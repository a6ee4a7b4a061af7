"""Layer: executor.  `setup_s` less the seconds of every set-up call:
interpreter, imports, program build, the harness's weights, feeds and
checks."""

import setup_spans


def read(ctx):
    calls = setup_spans.setup_calls(ctx)
    return None if calls is None else setup_spans.outside_s(
        ctx["result"]["setup_s"], calls)
