"""The one general generator of the benchmark's traffic.  A traffic mix is
a data file under perfbench/traffic/; this module turns its parameters and
a seed into inputs.  Nothing here knows a configuration or a cell by name.

Training mixes (`"kind": "train"`): `fields` names every feed of a step
and how it is drawn; a feed has the shape [steps_per_call, batch, len, 1].
A `len` or `high` given as a string is looked up in the traffic file and
then in the configuration.  Every seed gives the same sizes; only the
values differ, and every row of every step is drawn anew.  `feed_pool`
feeds are made and cycled through the window; set-up's frozen look at the
losses (drivers/train.py) goes through the first `frozen_calls` of them.
A mix that states a `data_seed` has its run's feeds drawn from that seed
and not from `--seed` (drivers/train.py `data_seed`): every run the same
batches."""

import numpy as np


def _lookup(value, traffic, cfg):
    if isinstance(value, str):
        return traffic[value] if value in traffic else cfg[value]
    return value


def _draw(spec, rng, shape, traffic, cfg):
    kind = spec["draw"]
    if kind == "token":
        return rng.integers(_lookup(spec["low"], traffic, cfg),
                            _lookup(spec["high"], traffic, cfg),
                            size=shape, dtype=np.int32)
    if kind == "position":
        return np.broadcast_to(
            np.arange(shape[-2], dtype=np.int32)[:, None], shape).copy()
    if kind == "zeros_int":
        return np.zeros(shape, np.int32)
    if kind == "ones":
        return np.ones(shape, np.float32)
    if kind == "bernoulli":
        return (rng.random(shape) < spec["p"]).astype(np.float32)
    raise ValueError(f"unknown draw {kind!r}")


def train_feeds(traffic, cfg, seed):
    """`feed_pool` feeds, each one `run_steps` call's worth of steps."""
    rng = np.random.default_rng([int(seed), 0x7261666669])
    steps, batch = traffic["steps_per_call"], traffic["batch"]
    feeds = []
    for _ in range(traffic["feed_pool"]):
        feed = {}
        for name in sorted(traffic["fields"]):
            spec = traffic["fields"][name]
            shape = (steps, batch, _lookup(spec["len"], traffic, cfg), 1)
            feed[name] = _draw(spec, rng, shape, traffic, cfg)
        feeds.append(feed)
    return feeds


def tokens_per_step(traffic, cfg):
    spec = traffic["fields"][traffic["count_field"]]
    return traffic["batch"] * _lookup(spec["len"], traffic, cfg)
