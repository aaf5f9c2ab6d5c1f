"""What one keyed ring write costs on the chip, outside every cell.

Chip tool (it prices a device path, so it runs where the default backend
is the TPU; here it only rehearses):

    chiprun -- python tools/ring_write_probe.py
    JAX_PLATFORMS=cpu python tools/ring_write_probe.py --sizes 65536 --calls 2

65,536 updates into a donated ring of ``n`` slots, ``n`` in 655,360 /
16,384,000 / 65,536,000 / 131,072,000 (``--sizes``): the slots drawn as
``partition_len1k_100k.hot20_bulk_100k`` draws them (20% of ``n / 1000``
keys take 80% of the rows; a row's slot is its key's ring at the key's
next arrival, unsorted; a row beyond a ring's 1,000 is not written),
every slot unique. Spellings of ``ring.at[slot].set(col, mode="drop")``:

- ``plain``: int64 slots, rows not written share ONE out-of-range slot,
  no flag: the write as ``ops/keyed_windows.py`` had it before PR 36 and
  as it still is beyond 31 bits of slots. The compiler picks the lowering
  by the ring's size (PERF.md section 7).
- ``a``: ``keyed_windows._plane_write`` itself, what ships: int32 slots,
  each leaf's ``(slot, word)`` sorted behind a barrier of its own, the
  scatter told ``indices_are_sorted, unique_indices``.
- ``b``: ONE sort of ``(slot, row number)`` shared by the leaves, each
  column gathered by that permutation, the scatter told the same.

Each for one ``u32`` leaf, one ``pred`` leaf, and the eleven leaves of the
benchmark's partitioned query together (8 x ``u32``, 3 x ``pred``). A line
of JSON a program: ms a call (mean of ``--calls`` after ``--warm``, each a
dispatch of its own waited for), seconds to compile, and off the SAME
compiled program: the scoped VMEM of the fusions that write a ring
(16,359,424 B is the windowed lowering, 135,168 B / 33,792 B the one that
goes update by update) and the operand counts of its sorts. All of it
also under ``--out`` (``chiprun_out/`` comes back from the chip).
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import siddhi_tpu  # noqa: E402,F401  (its XLA flags, before any JAX backend)

ROWS = 65_536
WINDOW = 1_000
ELEVEN = ("u32",) * 8 + ("pred",) * 3
SIZES = (655_360, 16_384_000, 65_536_000, 131_072_000)


def draw_slots(n: int, seed: int):
    """(int32 unique slots, int64 slots with the shared out-of-range one)
    of one hot20 batch into ``n // WINDOW`` rings that have wrapped."""
    rng = np.random.default_rng(seed)
    keys = n // WINDOW
    order = rng.permutation(keys)
    n_hot = max(1, keys // 5)
    hot = rng.random(ROWS) < 0.8
    key = np.where(hot, order[rng.integers(0, n_hot, ROWS)],
                   order[n_hot + rng.integers(0, keys - n_hot, ROWS)])
    by_key = np.argsort(key, kind="stable")
    first = np.r_[0, np.flatnonzero(np.diff(key[by_key])) + 1]
    rank = np.empty(ROWS, np.int64)
    rank[by_key] = np.arange(ROWS) - np.repeat(
        first, np.diff(np.r_[first, ROWS]))
    written = rank < WINDOW
    at = key * WINDOW + (rng.integers(0, WINDOW, keys)[key] + rank) % WINDOW
    unique = np.where(written, at, n + np.arange(ROWS))
    assert len(np.unique(unique)) == ROWS and n + ROWS <= np.iinfo(np.int32).max
    return unique.astype(np.int32), np.where(written, at, n)


def spellings():
    import jax.numpy as jnp
    from jax import lax

    from siddhi_tpu.ops.keyed_windows import _plane_write

    def plain(rings, slot, cols):
        return [r.at[slot].set(c, mode="drop") for r, c in zip(rings, cols)]

    def a(rings, slot, cols):
        return [_plane_write(r, slot, c) for r, c in zip(rings, cols)]

    def b(rings, slot, cols):
        slot, by = lax.sort((slot, jnp.arange(ROWS, dtype=jnp.int32)),
                            num_keys=1, is_stable=False)
        return [r.at[slot].set(c[by], mode="drop", indices_are_sorted=True,
                               unique_indices=True)
                for r, c in zip(rings, cols)]

    return {"plain": plain, "a": a, "b": b}


_RING_FUSION = r'= \w+\[{n}\]\S* fusion\(.*"used_scoped_memory_configs":\[([^\]]*)\]'
_SORT = re.compile(r" sort\(([^)]*)\)")


def read_compiled(text: str, n: int) -> dict:
    """Scoped VMEM of the fusions whose result is a ring, and the number
    of operands of every sort (empty off the TPU's compiler)."""
    vmem = sorted({int(size) for configs in re.findall(
        _RING_FUSION.format(n=n), text)
        for size in re.findall(r'"size":"(\d+)"', configs)})
    return {"ring_fusion_scoped_vmem_bytes": vmem,
            "sort_operands": sorted(len(m.split(", "))
                                    for m in _SORT.findall(text))}


def price(fn, leaves, n, slot, calls, warm, seed):
    import jax
    import jax.numpy as jnp

    dtypes = {"u32": jnp.uint32, "pred": jnp.bool_}
    rng = np.random.default_rng(seed)
    cols = [jnp.asarray(rng.integers(0, 2, ROWS).astype(bool) if d == "pred"
                        else rng.integers(0, 2**32, ROWS, dtype=np.uint32))
            for d in leaves]
    rings = [jnp.zeros((n,), dtypes[d]) for d in leaves]
    slot = jnp.asarray(slot)
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=0).lower(rings, slot, cols).compile()
    compile_s = time.perf_counter() - t0
    took = []
    for _ in range(warm + calls):
        t0 = time.perf_counter()
        rings = jax.block_until_ready(compiled(rings, slot, cols))
        took.append(time.perf_counter() - t0)
    at = np.asarray(slot)
    live = at < n
    for ring, col in zip(rings, cols):       # the write did what it says
        assert np.array_equal(np.asarray(ring[at[live]]),
                              np.asarray(col)[live])
    return {"ms_per_call": float(np.mean(took[warm:]) * 1e3),
            "compile_s": compile_s, **read_compiled(compiled.as_text(), n)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", type=int, nargs="+", default=list(SIZES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--warm", type=int, default=3)
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--out", default="chiprun_out/ring_write_probe.jsonl")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    device = jax.devices()[0]
    lines, written = [], spellings()
    for n in args.sizes:
        unique, shared = draw_slots(n, args.seed)
        for leaves in (("u32",), ("pred",), ELEVEN):
            for name, fn in written.items():
                got = {"device": device.device_kind, "slots": n,
                       "leaves": "+".join(leaves) if len(leaves) == 1
                       else "eleven", "spelling": name,
                       **price(fn, leaves, n,
                               shared if name == "plain" else unique,
                               args.calls, args.warm, args.seed)}
                print(json.dumps(got), flush=True)
                lines.append(got)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
