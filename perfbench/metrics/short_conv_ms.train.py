"""Layer: kernels.  Device time a step in the gated short-convolution
kernels of the convolution blocks (`short_conv_fwd`, `short_conv_bwd`).
Standard error gets the program's compile totals, where
`short_conv_sites_kernel` / `short_conv_sites_xla` say how many sites were
lowered to the kernels and how many to the XLA composition.  A program
without the kernels (the parent commit) gives None."""

import sys

import program_counters
import program_spans


def read(ctx):
    phases = program_spans.compile_phases()
    if phases:
        print(f"compile_phases {phases}", file=sys.stderr)
    return program_counters.kernel_ms(ctx, "short_conv_")
