"""Driver of the training cells (`"kind": "train"` traffic): one compiled
`Executor.run_steps` entry with its state, driven from the seed through
its first call, checked against the plain reference, and timed.

`TrainCell` is shared by the benchmark's run (`run`), by the calibration
tool that reads a dozen seeds, the control and the faults in one process
(perfbench/calibrate.py) and by the tests."""

import contextlib
import functools
import math
import shutil
import time

import numpy as np

import compare
import traffic_gen
from registry import load_module


def weights_seed(cfg, seed):
    """The seed a run's weights are drawn from: its `--seed`, unless the
    configuration states one draw for every run (`weights_seed`; PERF.md
    section 7 (10) says why a cell would).  The program's own key follows
    `--seed` either way."""
    return cfg.get("weights_seed", seed)


def data_seed(traffic, seed):
    """The seed a run's feeds are drawn from: its `--seed`, unless the
    traffic mix states one set of batches for every run (`data_seed`).
    perfbench/calibrate.py does not read the key: it goes on sweeping."""
    return traffic.get("data_seed", seed)


class TrainCell:
    def __init__(self, cell):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as pt

        from reference import blocks

        self.cfg, self.traffic = cell.cfg, cell.traffic
        self.jax, self.blocks = jax, blocks
        self.ref_mod = load_module(cell.path(self.cfg["reference"]))
        prog_mod = load_module(cell.path(self.cfg["program"]))
        self.prog, self.startup, self.loss = prog_mod.build(
            self.cfg, self.traffic)
        self.leaves = self.ref_mod.leaves(self.cfg, self.traffic)
        self.trainable = [n for n, _, _, t in self.leaves if t]
        self.scope, self.exe = pt.Scope(), pt.Executor()
        self._bind()
        # jitted once: every run calls each of these several times
        self._init = blocks.make_init(self.leaves)
        self._ref_steps = {}
        self._norms = jax.jit(lambda xs: [jnp.linalg.norm(x.ravel())
                                          for x in xs])
        self._diff_norms = jax.jit(lambda xs, ys: [
            jnp.linalg.norm((x - y).ravel()) for x, y in zip(xs, ys)])

    def _bind(self):
        """Tie each leaf of the reference to the program's parameter of
        the same place and shape, and to its Adam moment."""
        block = self.prog.global_block()
        params = block.all_parameters()
        if len(params) != len(self.leaves):
            raise RuntimeError(
                f"the program has {len(params)} parameters, the reference "
                f"{len(self.leaves)} leaves")
        self.param_of = {}
        for p, (name, shape, _, trainable) in zip(params, self.leaves):
            if tuple(p.shape) != tuple(shape) or bool(
                    getattr(p, "trainable", True)) != trainable:
                raise RuntimeError(
                    f"parameter {p.name} {tuple(p.shape)} does not match "
                    f"leaf {name} {tuple(shape)}")
            self.param_of[name] = p.name
        adam = [op for op in block.ops if op.type == "adam"]
        moment = {op.input("Param")[0]: op.input("Moment1")[0] for op in adam}
        self.moment_of = {n: moment[self.param_of[n]] for n in self.trainable}
        self.lr_names = sorted({op.input("LearningRate")[0] for op in adam})

    def init_params(self, seed):
        return self._init(self.blocks.seed_key(weights_seed(self.cfg, seed)))

    def _listed(self, tree):
        return [tree[n] for n in self.trainable]

    def _named(self, values):
        return dict(zip(self.trainable, map(float, values)))

    def reset(self, seed, frozen=False):
        """Start-up, then the benchmark's own weights from the seed.  With
        `frozen` the learning rate, a variable of the scope, is set to
        nought: the same compiled call then leaves the weights where they
        are while Adam's moments gather the gradients at them."""
        self.prog.random_seed = int(seed) % (2 ** 31)
        self.exe.run(self.startup, scope=self.scope)
        for name, value in self.init_params(seed).items():
            self.scope.set_var(self.param_of[name], value)
        if frozen:
            for name in self.lr_names:
                lr = self.scope.find_var(name)
                self.scope.set_var(name, self.jax.numpy.zeros_like(lr))

    def call(self, feed):
        """The timed entry.  Returns the per-step losses."""
        (losses,) = self.exe.run_steps(self.prog, feed=feed,
                                       fetch_list=[self.loss],
                                       scope=self.scope)
        return np.asarray(losses, np.float64).reshape(-1)

    def observe(self, seed, losses, moments=False):
        """What a call from fresh state left in the scope, as per-leaf
        norms; with `moments` also Adam's first moments, on the host."""
        params = [self.scope.find_var(self.param_of[n])
                  for n in self.trainable]
        moms = [self.scope.find_var(self.moment_of[n])
                for n in self.trainable]
        out = {"losses": [float(x) for x in losses],
               "m_norm": self._named(self._norms(moms)),
               "dp_norm": self._named(self._diff_norms(
                   params, self._listed(self.init_params(seed))))}
        if moments:
            out["m_host"] = dict(zip(self.trainable, map(np.asarray, moms)))
        return out

    def first_calls(self, seed, feeds, call=None):
        """Drive the compiled entry from the seed through its first call,
        twice from fresh state: once frozen (learning rate nought), once as
        the cell states.  The second call's state is what the window goes
        on from.  Frozen, the entry goes on through the traffic's
        `frozen_calls` feeds of the pool (one where it names none): the
        weights stay, so every step's loss is one more forward pass at
        them.  `call` stands in for `self.call` (a run's counting wrapper
        round it)."""
        call = call or self.call
        self.reset(seed, frozen=True)
        frozen = self.observe(seed, call(feeds[0]), moments=True)
        for feed in feeds[1:self.traffic.get("frozen_calls", 1)]:
            frozen["losses"] += [float(x) for x in call(feed)]
        self.reset(seed)
        return {"frozen": frozen,
                "trained": self.observe(seed, call(feeds[0]))}

    def free(self):
        """Drop the program's state, so that the reference fits."""
        self.scope, self.exe = None, None

    def reference(self, seed, feeds, mode="f32"):
        """The plain reference (or, with `mode="fp8"`, the control) from
        the same weights over the same feeds, frozen and trained, as
        `first_calls` drives the program."""
        blocks, jnp = self.blocks, self.jax.numpy
        if mode not in self._ref_steps:
            loss_sum = functools.partial(self.ref_mod.loss_sum,
                                         blocks.Dots(mode), self.cfg)
            rows = self.traffic["reference_rows_per_block"]
            self._ref_steps[mode] = (
                blocks.make_train_step(loss_sum, self.ref_mod.WEIGHTS_FIELD,
                                       self.trainable, rows),
                blocks.make_loss(loss_sum, self.ref_mod.WEIGHTS_FIELD, rows))
        step, loss_only = self._ref_steps[mode]
        n_steps = self.traffic["steps_per_call"]

        def batch_of(feed, t):
            return {k: jnp.asarray(x[t]) for k, x in feed.items()}

        def follow(lr):
            params = self.init_params(seed)
            m = {k: jnp.zeros_like(params[k]) for k in self.trainable}
            v = {k: jnp.zeros_like(params[k]) for k in self.trainable}
            losses, g1 = [], None
            for t in range(n_steps):
                loss, params, m, v, gnorm = step(
                    params, m, v, jnp.float32(t + 1), jnp.float32(lr),
                    batch_of(feeds[0], t))
                losses.append(float(loss))
                if t == 0:
                    g1 = {k: float(x) for k, x in gnorm.items()}
            out = {"losses": losses, "g1_norm": g1,
                   "m_norm": self._named(self._norms(self._listed(m))),
                   "dp_norm": self._named(self._diff_norms(
                       self._listed(params),
                       self._listed(self.init_params(seed))))}
            return out, m

        frozen, m = follow(0.0)
        frozen["m_host"] = {k: np.asarray(x) for k, x in m.items()}
        del m
        params = self.init_params(seed)
        for feed in feeds[1:self.traffic.get("frozen_calls", 1)]:
            frozen["losses"] += [float(loss_only(params, batch_of(feed, t)))
                                 for t in range(n_steps)]
        del params
        trained, _ = follow(self.traffic["learning_rate"])
        return {"frozen": frozen, "trained": trained}


def numbers_of(obs, ref):
    """`compare.train_numbers` of an observation (the program's, or the
    control's put in its place) against the reference."""
    mine, theirs = obs["frozen"]["m_host"], ref["frozen"]["m_host"]
    frozen = dict(obs["frozen"], m_diff_norm={
        k: float(np.linalg.norm(
            (mine[k].astype(np.float64) - theirs[k]).ravel()))
        for k in theirs})
    return compare.train_numbers(dict(obs, frozen=frozen), ref)


def device_peak_parts(device):
    """The two peaks the TPU client keeps: `peak_bytes_in_use` counts live
    arrays, `peak_bytes_reserved` the space its loaded executables hold for
    their temporaries (PERF.md, section 6, has the probe).  A step holds
    both at once, so the device's peak is taken as their sum; the two are
    peaks of different moments, so the sum is an upper reading, and the
    result line carries the parts."""
    stats = device.memory_stats() or {}
    return {k: int(stats.get(k, 0))
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def half_batch(feed, weights_field):
    """The fault `half of the batch left out, the mean taken over the
    rest`, planted in one call's feed: the second half's weights are
    nought."""
    out = dict(feed)
    w = feed[weights_field].copy()
    w[:, w.shape[1] // 2:] = 0
    out[weights_field] = w
    return out


def run(cell, seed, seconds, trace, compile_cache=True, cell_class=TrainCell):
    """One run of a training cell.  `compile_cache=False` and `cell_class`
    are for the tests: no persistent cache on the CPU, and a `TrainCell`
    whose timed entry is broken underneath the run."""
    import jax

    if compile_cache:
        from paddle_tpu.inference import enable_compile_cache

        enable_compile_cache()
    tc = cell_class(cell)
    traffic = cell.traffic
    feeds = traffic_gen.train_feeds(traffic, cell.cfg,
                                    data_seed(traffic, seed))

    steps = traffic["steps_per_call"]
    tokens_call = steps * traffic_gen.tokens_per_step(traffic, cell.cfg)
    annotate = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())
    attempted = failed = 0

    def counted(feed):
        """One call of the timed entry; a non-finite loss fails it."""
        nonlocal attempted, failed
        attempted += 1
        with annotate("pb.run_steps"):
            losses = tc.call(feed)
        with annotate("pb.fetch"):
            failed += not np.all(np.isfinite(losses))
        return losses

    def loop(budget_s, max_calls=math.inf):
        """Closed loop: the next call when the last has returned.  Returns
        (calls, seconds to the end of the last completed call)."""
        n, t0 = 0, time.perf_counter()
        t_end = t0
        while n < max_calls and time.perf_counter() - t0 < budget_s:
            with annotate("pb.feed"):
                feed = feeds[attempted % len(feeds)]
            counted(feed)
            n += 1
            t_end = time.perf_counter()
        return n, t_end - t0

    first = tc.first_calls(seed, feeds, call=counted)
    warm_calls, warm_s = loop(math.inf, traffic["warm_calls"])
    setup_s = time.time() - cell.t_start

    if trace:
        # the last `trace_calls` calls of the window are traced; the rate
        # comes from the calls before them, which no profiler has touched
        seconds = max(
            seconds - traffic["trace_calls"] * warm_s / warm_calls, 1.0)
    n_calls, window_s = loop(seconds)
    rate = n_calls * tokens_call / window_s
    traced = None
    if trace:
        trace_dir = cell.out_path("trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        with annotate("pb.window"):
            n_traced, traced_s = loop(math.inf, traffic["trace_calls"])
        jax.profiler.stop_trace()
        traced = {"dir": trace_dir, "calls": n_traced, "window_s": traced_s,
                  "steps": n_traced * steps}

    parts = max((device_peak_parts(d)
                 for d in jax.local_devices()[:cell.chips]),
                key=lambda p: sum(p.values()))
    tc.free()

    ref = tc.reference(seed, feeds)
    numbers, where = numbers_of(first, ref)
    ok, compared = compare.verdict(numbers, cell.limits)
    return {
        "correct": bool(ok and failed == 0),
        "attempted": attempted, "failed": failed,
        "setup_s": setup_s, "train_tokens_per_s": rate,
        "window": {"calls": n_calls, "seconds": window_s, "steps":
                   n_calls * steps, "tokens_per_call": tokens_call},
        "memory_peak_bytes": sum(parts.values()), "memory_parts": parts,
        "traced": traced,
        "compared": compared, "where": where,
        "seeds": {"weights": weights_seed(cell.cfg, seed),
                  "data": data_seed(traffic, seed)},
    }
