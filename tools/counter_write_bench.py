"""Chip micro-benchmark behind ``ops/windows.py``'s ``COUNTERS_PER_WRITE``:
what it costs to move a batch's streams on in the ``[T, S]`` counters.

Three ways, each timed as ten updates inside one jitted call:

- ``whole``: the parent's form (PR 39) — a per-stream count built
  densely per slot and added to both arrays whole;
- ``one_by_one``: each written stream's counters in place, one
  ``dynamic-update-slice`` a stream, in a ``fori_loop``;
- ``repo``: ``ops.windows._write_counters`` as the step calls it, which
  picks between the two from the counters' size.

For each counter size T x S it times ``whole`` at 13 written streams and
``one_by_one`` / ``repo`` at a few counts of streams, and reports the
device bytes the loaded program takes beside its
``generated_code_size_in_bytes``. Run it on the chip:

    chiprun -- python tools/counter_write_bench.py

One JSON line per reading on stdout, the same lines in
``chiprun_out/counter_write_bench.jsonl``. ``small`` as the first
argument runs tiny sizes, for the CPU.
"""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from sitewhere_tpu.ops import windows  # noqa: E402

W = 32
R = 10  # updates a call
SMALL = len(sys.argv) > 1 and sys.argv[1] == "small"
T = 4 if SMALL else 32
SIZES = (256, 5000) if SMALL else (2048, 4096, 65536, 1_171_875)
WRITERS = (1, 13) if SMALL else (1, 13, 128, 1024)
OUT = Path("chiprun_out/counter_write_bench.jsonl")


def whole(pos, count, ids, new, valid):
    s = pos.shape[-1]
    per = jax.vmap(lambda i, v: jnp.zeros((s,), jnp.int32).at[
        jnp.where(v, i, s)].add(1, mode="drop"))(ids, valid)
    return (pos + per) % W, count + per


def one_by_one(pos, count, ids, new, valid):
    t = jnp.broadcast_to(jnp.arange(T)[:, None], ids.shape).reshape(-1)
    i, p1, v = ids.reshape(-1), new.reshape(-1), valid.reshape(-1)
    order = jnp.argsort(~v, stable=True)
    n = v.sum()

    def body(k, carry):
        j = order[k]
        at = (t[j], i[j])
        return tuple(lax.dynamic_update_slice(c, p1[j][None, None], at)
                     for c in carry)

    return lax.fori_loop(0, n, body, (pos, count))


def repo(pos, count, ids, new, valid):
    state = windows.WindowState(
        values=jnp.zeros((T, 8, windows.LANES), jnp.float32),
        pos=pos, count=count, window=W)
    ones = jnp.ones_like(ids)
    return windows._write_counters(
        state, ids, valid, ones - 1, ones, new % W, new)


def timed(f, s, writers):
    """ms an update, the device bytes in use after the program's first
    run over those before its compile, and its code size."""
    b = max(1024, -(-writers // T))
    ids = (jnp.arange(T * b, dtype=jnp.int32).reshape(T, b) * 7919) % s
    valid = jnp.arange(T * b).reshape(T, b) < writers

    def run(pos, count, ids, valid):
        def one(r, carry):
            rid = (ids + r * 104729) % s  # distinct ids a slot, each call
            return f(*carry, rid, rid + r, valid)
        return lax.fori_loop(0, R, one, (pos, count))

    dev = jax.devices()[0]
    pos = jnp.zeros((T, s), jnp.int32)
    count = jnp.zeros((T, s), jnp.int32)
    jax.block_until_ready(count)
    before = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    fn = jax.jit(run, donate_argnums=(0, 1)).lower(
        pos, count, ids, valid).compile()
    code = fn.memory_analysis().generated_code_size_in_bytes
    pos, count = fn(pos, count, ids, valid)  # warm
    jax.block_until_ready(count)
    # the counters were donated to outputs of their own size: what is in
    # use beyond them now is what the loaded program keeps
    loaded = (dev.memory_stats() or {}).get("bytes_in_use", 0) - before
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        pos, count = fn(pos, count, ids, valid)
    jax.block_until_ready(count)
    ms = (time.perf_counter() - t0) * 1e3 / (reps * R)
    return ms, loaded, code


def main():
    OUT.parent.mkdir(exist_ok=True)
    dev = jax.devices()[0]
    with OUT.open("w") as out:
        for s in SIZES:
            most = T * s // windows.COUNTERS_PER_WRITE
            cases = [("whole", whole, 13)]
            cases += [("one_by_one", one_by_one, n) for n in WRITERS]
            cases += [("repo", repo, n) for n in WRITERS]
            if 0 < most < T * 1024 and most + 1 not in WRITERS:
                cases.append(("repo", repo, most + 1))  # just past it
            for name, f, n in cases:
                ms, loaded, code = timed(f, s, n)
                line = {"variant": name, "t": T, "s": s, "writers": n,
                        "most": most, "ms_per_update": ms,
                        "loaded_bytes": loaded, "code_bytes": code,
                        "device": dev.device_kind}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
