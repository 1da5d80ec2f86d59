"""Chip micro-benchmark behind ``models.common.sketch_histogram``: what
binning a step's score plane into the per-slot sketch costs.

Three ways, each timed as twenty sketches inside one jitted call, over a
``[32, B]`` bf16 score plane with 1% of its lanes valid:

- ``search``: the former form — ``jnp.searchsorted`` over the 63 edges
  (a binary search, one element gather of the edges a round) and one
  ``segment_sum`` into 32 x 65 bins;
- ``compare``: ``sketch_histogram`` as the step calls it — each score
  compared with each edge, counts reduced over the lanes;
- ``onehot``: each score's bin from the same comparisons, then one
  reduction over the lanes a bin.

Run it on a TPU:

    python tools/sketch_bench.py

One JSON line per reading on stdout; each way's histogram of the plane
is checked equal to ``search``'s. ``small`` as the first argument runs tiny
sizes, for the CPU.
"""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from sitewhere_tpu.models.common import (  # noqa: E402
    sketch_edges,
    sketch_histogram,
)

R = 20  # sketches a call
SMALL = len(sys.argv) > 1 and sys.argv[1] == "small"
T = 4 if SMALL else 32
LANES = (256,) if SMALL else (1024, 4096, 16384)
EDGES = sketch_edges()


def search(s, valid, edges):
    nbins = len(edges) + 1
    sf = s.astype(jnp.float32)
    b = jnp.searchsorted(jnp.asarray(edges), sf, side="right")
    b = jnp.where(valid & ~jnp.isnan(sf), b.astype(jnp.int32), nbins)
    flat = (jnp.arange(T, dtype=jnp.int32)[:, None] * (nbins + 1)
            + b).reshape(-1)
    hist = jax.ops.segment_sum(
        jnp.ones_like(flat), flat, num_segments=T * (nbins + 1))
    return hist.reshape(T, nbins + 1)[:, :nbins]


def onehot(s, valid, edges):
    nbins = len(edges) + 1
    sf = s.astype(jnp.float32)
    e = jnp.asarray(edges, jnp.float32)
    b = jnp.sum(sf[..., None] >= e, axis=-1, dtype=jnp.int32)
    b = jnp.where(valid & ~jnp.isnan(sf), b, nbins)
    k = jnp.arange(nbins, dtype=jnp.int32)
    return jnp.sum(b[:, None, :] == k[None, :, None], axis=-1,
                   dtype=jnp.int32)


WAYS = {"search": search, "compare": sketch_histogram, "onehot": onehot}


def timed(f, s, valid):
    """ms a sketch, and the first sketch."""
    def run(s, valid):
        def one(r, acc):
            # a different plane each round, so nothing is hoisted
            sr = (s.astype(jnp.float32) * (1 + r / 64)).astype(s.dtype)
            return acc + f(sr, valid, EDGES)
        return lax.fori_loop(0, R, one, jnp.zeros((T, 64), jnp.int32))

    fn = jax.jit(run).lower(s, valid).compile()
    first = np.asarray(jax.jit(f)(s, valid, EDGES))
    jax.block_until_ready(fn(s, valid))  # warm
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(s, valid)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / (reps * R), first


def main():
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    for b in LANES:
        s = jnp.asarray(rng.lognormal(-1.0, 2.0, (T, b)), jnp.bfloat16)
        valid = jnp.asarray(rng.random((T, b)) < 0.01)
        ref = None
        for name, f in WAYS.items():
            ms, first = timed(f, s, valid)
            ref = first if ref is None else ref
            print(json.dumps({
                "way": name, "slots": T, "lanes": b, "ms": round(ms, 5),
                "equal": bool((first == ref).all()),
                "device": dev.device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
