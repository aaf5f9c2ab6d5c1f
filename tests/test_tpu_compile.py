"""What the TPU's compiler says about the main steps — asked here, with
no chip attached (on-chip-measurement guide, section 2, third rehearsal).

A v5e:2x2 topology is DESCRIBED inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker imports
this file). Each test drives the real app through the normal entry points
on the CPU backend once, records the exact ``(state, cols, now)`` the
engine hands its jitted step, and lowers that same jitted callable for a
described chip. Nothing runs on a TPU here: a compile that passes is not
a chip run.

All such compiles live in this ONE file, so one worker holds the library.
"""

import re
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import SingleDeviceSharding

from siddhi_tpu import SiddhiManager
from siddhi_tpu.ops.expressions import PADDED_KEY

_STOCK = """
@app:precision('{precision}')
define stream StockStream (symbol string, price {price}, volume long);
{head}
  @info(name = 'bench')
  from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
  {group}
  insert into OutStream;
{tail}
"""

_PATTERN = """
@app:playback
define stream AStream (k string, v double);
define stream BStream (k string, v double);
partition with (k of AStream, k of BStream)
begin
  @info(name = 'nfa')
  from every e1=AStream -> e2=BStream[e2.v > e1.v] within 5 sec
  select e1.v as v1, e2.v as v2
  insert into MatchStream;
end;
"""


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spy_steps(q):
    """Record every ``step(state, cols, now)`` the runtime dispatches:
    the jitted callable and the avals of its arguments."""
    seen = []
    finish = q._finish_device_batch

    def spying_finish(step, cols, overflow_msg):
        def spy(*args):
            seen.append((step, _avals(args)))
            return step(*args)

        return finish(spy, cols, overflow_msg)

    q._finish_device_batch = spying_finish
    return seen


def _avals(args):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.result_type(x)), args)


class _SpyingSteps(dict):
    """``NFAQueryRuntime._steps`` stand-in: remembers, per (stream,
    generic) key, the jitted step and the avals of its last call."""

    def __init__(self):
        super().__init__()
        self.seen = {}

    def __setitem__(self, key, step):
        def spy(*args):
            self.seen[key] = (step, _avals(args))
            return step(*args)

        super().__setitem__(key, spy)


def _compile_for(sharding, step, avals):
    """Lower the engine's own jitted step for a described device."""
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        avals)
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _report(name, compiled, seconds):
    m = compiled.memory_analysis()
    print(f"\n[tpu-compile] {name}: {seconds:.1f} s, "
          f"code {m.generated_code_size_in_bytes / 1e6:.1f} MB, "
          f"args {m.argument_size_in_bytes / 1e6:.1f} MB, "
          f"out {m.output_size_in_bytes / 1e6:.1f} MB, "
          f"temp {m.temp_size_in_bytes / 1e6:.1f} MB")
    assert m.generated_code_size_in_bytes > 0
    return m


_SCATTER = re.compile(
    r'= (.*?) (?:scatter|fusion)\(.*op_name="[^"]*/(siddhi\.\w+)/(scatter(?:-add)?)"')


def _scatters(hlo_text):
    """``(scope, primitive, result type)`` of every scatter of a compiled
    step, layouts stripped: ``("siddhi.state", "scatter", "s32[129]")``;
    an emulated 64-bit one reads ``(u32[128000], u32[128000])``."""
    return [(m[2], m[3], re.sub(r"\{[^}]*\}", "", m[1]))
            for m in map(_SCATTER.search, hlo_text.splitlines()) if m]


def _scatter_ops(hlo_text):
    """``_scatters`` of the scatter instructions alone: one entry for
    every scatter the chip runs (``_scatters`` also sees the fusion that
    holds it, and fusions that only prepare its indices, under the same
    ``op_name``)."""
    return _scatters("\n".join(
        line for line in hlo_text.splitlines() if " scatter(" in line))


def _stock_step(precision, partitioned, window, keys, batch, price="float"):
    """The last step dispatch of one warm batch that covers every key at
    the measured batch shape — what chip_smoke.py compiles in warm-up."""
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(_STOCK.format(
        precision=precision, W=window, price=price,
        head="partition with (symbol of StockStream)\nbegin"
        if partitioned else "",
        group="" if partitioned else "group by symbol",
        tail="end;" if partitioned else ""))
    seen = _spy_steps(rt.query_runtimes["bench"])
    symbols = np.array([f"S{i}" for i in range(keys)], dtype=object)
    rt.get_input_handler("StockStream").send_columns(
        {"symbol": symbols[np.arange(batch) % keys],
         "price": np.ones(batch, np.float32),
         "volume": np.ones(batch, np.int64)},
        timestamps=np.zeros(batch, np.int64))
    manager.shutdown()
    return seen[-1]


# (a) phase A: global length(1000) -> avg/sum group by symbol. "fast" is
# what the chip's users get by default; tier-1 keeps it at the largest
# batch that compiles in about ten seconds, the full shapes are `slow`.
@pytest.mark.parametrize("precision,keys,batch", [
    ("fast", 1_000, 2_048),
    pytest.param("fast", 10_000, 65_536, marks=pytest.mark.slow),
    pytest.param("exact", 10_000, 65_536, marks=pytest.mark.slow),
])
def test_global_window_step_compiles(one_chip, precision, keys, batch):
    step, avals = _stock_step(precision, False, 1_000, keys, batch)
    compiled, seconds = _compile_for(one_chip, step, avals)
    _report(f"A length(1000) group-by {precision} B={batch} keys={keys}",
            compiled, seconds)


# (c) phase B: per-key rings [K * 1000]; the tier-1 case is the size that
# fits a test's time, the deployment size is `slow`.
@pytest.mark.parametrize("keys,batch,price", [
    (100, 1_024, "float"),
    (100, 1_024, "double"),
    pytest.param(10_000, 65_536, "float", marks=pytest.mark.slow),
])
def test_keyed_ring_step_compiles(one_chip, keys, batch, price):
    step, avals = _stock_step("fast", True, 1_000, keys, batch, price)
    compiled, seconds = _compile_for(one_chip, step, avals)
    m = _report(f"B partitioned length(1000) B={batch} keys={keys} "
                f"price {price}", compiled, seconds)
    rows = max(a.shape[0] for a in jax.tree_util.tree_leaves(avals[0]["win"]))
    assert rows >= keys * 1_000          # the rings really are [K * W]
    assert m.argument_size_in_bytes > rows
    # K-wide state (the aggregates, the per-key counts) is written back
    # from the sorted batch's segment ends: at most ONE one-operand 32-bit
    # scatter of positions or counts into [K + 1]. A tuple result is an
    # emulated 64-bit value (two 32-bit planes), which gets no sorted path
    # on the chip: 71-96 ns an update against 5 (PERF.md, PR 26).
    capacity = avals[0]["sel"]["a0"].shape[1]
    text = compiled.as_text()
    scatters = _scatters(text)
    assert any(scope == "siddhi.state" for scope, _, _ in scatters)
    for scope, primitive, result in scatters:
        if scope == "siddhi.select":
            assert (primitive, result) == ("scatter", f"s32[{capacity + 1}]"), (
                f"a batch-wide write-back into selector state is back: "
                f"{scope}/{primitive} -> {result}")
        elif primitive == "scatter-add":
            assert not result.startswith("("), (
                f"a 64-bit histogram is back: {scope}/{primitive} -> {result}")
    _assert_int64_rings_are_word_leaves(
        text, rows, doubles=int(price == "double"))
    _assert_ring_writes_are_told_their_slots(
        text, rows, doubles=int(price == "double"))


_RING_WRITE = "siddhi.ring_write"
# what a ring write's fusion holds of VMEM says how it is lowered (PERF.md
# section 7): windows of the ring passed through it, or nothing but the
# updates, which then go one after another (85-93 ns each on the chip)
_WINDOWED_VMEM, _SERIAL_VMEM = 16_359_424, 135_168
_OPERANDS = re.compile(r" [\w-]+\((%[^)]*)\)")


def _operands(definition):
    """The operand names of an instruction's right-hand side."""
    found = _OPERANDS.search(definition)
    return found[1].split(", ") if found else []


def _donated_leaf(defined, fusion_line):
    """``(type, parameter number)`` of the parameter behind a ring write's
    first operand, which the fusion aliases to its result: the donated
    state's own leaf, alone or staged into fast memory (whole by a copy,
    or in slices put together by a ``ConcatBitcast``), never a fresh copy
    of the ring."""
    assert '"aliasing_operands":{"lists":[{"indices":["0",' in fusion_line, (
        fusion_line)
    operand = _operands(fusion_line)[0]
    while not (leaf := re.match(r"(\w+\[\d+\])\S* parameter\((\d+)\)",
                                defined[operand])):
        assert re.search(r" (?:copy|slice)-(?:done|start)\(|ConcatBitcast",
                         defined[operand]), (operand, defined[operand])
        operand = _operands(defined[operand])[0]
    return leaf[1], leaf[2]


def _sorts_behind(defined, name):
    """The right-hand sides of the sorts that ``name`` is computed from."""
    seen, todo, sorts = set(), [name], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in defined:
            continue
        seen.add(name)
        if " sort(" in defined[name]:
            sorts.add(defined[name])
        else:
            todo += _operands(defined[name])
    return sorts


def _assert_ring_writes_are_told_their_slots(hlo_text, rows, doubles=0):
    """Every ring leaf of the step (eleven: ``symbol``, ``price``, the
    word leaves of ``volume`` and ``__ts__``, ``__pk__``, ``__gk__``,
    three null masks) is written under ``siddhi.ring_write`` by a scatter
    that is TOLD its slots are sorted and unique, behind a sort of its
    own of (slot, word): two operands, three where the word is a
    ``double``'s pair of planes. Sorts that share their key merge into one
    with every column as payload unless each is kept apart, and that one
    compiles in minutes (``ops/keyed_windows.py``; PERF.md, PR 28)."""
    lines = hlo_text.splitlines()
    writes = [line for line in lines if " scatter(" in line
              and f'/{_RING_WRITE}/scatter"' in line]
    assert len(writes) == 11, writes
    for line in writes:
        assert re.search(rf"= \(?\w+\[{rows}\]", line), line
        assert "indices_are_sorted=true, unique_indices=true" in line, line
    sorts = [line for line in lines if " sort(" in line
             and f'/{_RING_WRITE}/sort"' in line]
    assert sorted(len(_operands(line)) for line in sorts) == (
        [2] * (11 - doubles) + [3] * doubles), sorts


_X64_CALL = re.compile(r'custom_call_target="(X64SplitLow|X64SplitHigh|X64Combine)"')
_DEFINITION = re.compile(r"^\s*(?:ROOT )?(%[\w.-]+) = (.*)$")


def _assert_int64_rings_are_word_leaves(hlo_text, rows, doubles=0):
    """The ring layout of ``ops/keyed_windows.py``: each of the two int64
    ring columns (``__ts__``, ``volume``) is two ``u32[rows]`` leaves of
    the state, each written by a one-operand scatter whose operand is a
    parameter of the program (the donated state's own leaf, updated in
    place: 146 ns an update as one two-plane scatter against 5-8, PERF.md
    section 5, PR 32). Nothing takes a ring apart or puts one together:
    no ``X64SplitLow`` / ``X64SplitHigh`` / ``X64Combine`` custom call
    (timed copies on the chip) and no elementwise fusion as long as a
    ring (33 of cell 6's 152 ms step before PR 34). The exception,
    written down: a ``double`` is a pair of float32 on the chip with no
    bits to take (no bitcast-convert from f64 there), so each of the
    ``doubles`` ring columns stays ONE two-plane write; nothing else
    under ``siddhi.state`` writes two planes."""
    lines = hlo_text.splitlines()
    scatters = _scatter_ops(hlo_text)
    ring = [result for scope, primitive, result in scatters
            if scope == _RING_WRITE and primitive == "scatter"]
    assert [r for r in ring if r.startswith("(")] == [
        f"(f32[{rows}], f32[{rows}])"] * doubles, ring
    assert ring.count(f"u32[{rows}]") == 4, ring
    assert not [s for s in scatters if s[0] == "siddhi.state"
                and (s[2].startswith("(") or f"[{rows}]" in s[2])], scatters
    # the four fusions that hold those scatters, in the entry computation:
    # the ring operand of each is the state's own leaf, alone or staged
    # into fast memory by an asynchronous copy (a small ring), and is
    # aliased to the fusion's result
    defined = {m[1]: m[2] for m in map(_DEFINITION.search, lines) if m}
    word_writes = [line for line in lines if re.search(
        rf'= u32\[{rows}\]\S* fusion\(.*/{_RING_WRITE}/scatter"', line)]
    assert len(word_writes) == 4, word_writes
    leaves = {_donated_leaf(defined, line) for line in word_writes}
    assert len(leaves) == 4 and {t for t, _ in leaves} == {f"u32[{rows}]"}, (
        leaves)
    # (a ``double`` ring is a pair of planes to the compiler, which takes
    # it apart and puts it together itself: the ``doubles``' own calls)
    x64 = [line for line in lines if _X64_CALL.search(line)
           and f"[{rows}]" in line]
    assert len(x64) == 3 * doubles and not [
        line for line in x64 if re.search(rf"[su](?:32|64)\[{rows}\]", line)], x64
    passes = [line for line in lines
              if re.search(rf"= \(?\w+\[{rows}\]", line)
              and " fusion(" in line and "kind=kLoop" in line]
    assert not passes, passes


_RING_SIZES = (655_360, 16_384_000, 131_072_000)


def _ring_avals(one_chip, dtype, n, slot_dtype="int32", rows=65_536):
    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return aval((n,), dtype), aval((rows,), slot_dtype), aval((rows,), dtype)


@pytest.fixture(scope="module")
def ring_writes(one_chip):
    """ONE program: ``keyed_windows._ring_write`` of 65,536 updates into a
    donated ``u32[n]`` and a donated ``pred[n]`` for the three ``n``, each
    ring with slots and words of its own. (The six sorts share a
    signature, so the program compiles in about what one ring takes.)"""
    from siddhi_tpu.ops.keyed_windows import _ring_write

    args = {f"{dtype}[{n}]": _ring_avals(one_chip, dtype, n)
            for n in _RING_SIZES for dtype in ("uint32", "bool")}
    rings, slots, words = ({k: v[i] for k, v in args.items()}
                           for i in range(3))

    def write(rings, slots, words):
        return {k: _ring_write(rings[k], slots[k], words[k]) for k in rings}

    t0 = time.perf_counter()
    compiled = jax.jit(write, donate_argnums=0).lower(
        rings, slots, words).compile()
    _report("six ring writes of 65,536 updates", compiled,
            time.perf_counter() - t0)
    return compiled.as_text()


def _ring_write_fusion(hlo_text, result):
    """(fusion line, {name: definition}) of the one fusion whose result is
    a ring of type ``result`` and whose root is a scatter."""
    lines = hlo_text.splitlines()
    found = [line for line in lines if re.search(
        rf'= {re.escape(result)}\S* fusion\(.*/scatter"', line)]
    assert len(found) == 1, found
    return found[0], {m[1]: m[2] for m in map(_DEFINITION.search, lines) if m}


def _scoped_vmem(fusion_line):
    return [int(size) for size in re.findall(
        r'"size":"(\d+)"', re.search(
            r'"used_scoped_memory_configs":\[([^\]]*)\]', fusion_line)[1])]


# (f) the ring write alone, at a ring staged whole in VMEM, at cells 1 and
# 4's ring (where the compiler sorts and windows by itself) and at cell
# 6's (where, left alone, it goes update by update: the last case).
@pytest.mark.parametrize("n", _RING_SIZES)
@pytest.mark.parametrize("hlo_dtype", ["u32", "pred"])
def test_a_ring_write_is_told_its_slots_and_windowed(ring_writes, hlo_dtype, n):
    result = f"{hlo_dtype}[{n}]"
    scatter, = [line for line in ring_writes.splitlines()
                if re.search(rf"= {re.escape(result)}\S* scatter\(", line)]
    assert f'/{_RING_WRITE}/scatter"' in scatter
    assert "indices_are_sorted=true, unique_indices=true" in scatter
    fusion, defined = _ring_write_fusion(ring_writes, result)
    assert _donated_leaf(defined, fusion)[0] == result
    sorts = set().union(*(_sorts_behind(defined, operand)
                          for operand in _operands(fusion)[1:]))
    assert [len(_operands(rhs)) for rhs in sorts] == [2], sorts
    if n > 655_360:
        assert _scoped_vmem(fusion) == [_WINDOWED_VMEM]


def test_a_plain_write_of_cell_6s_ring_still_goes_update_by_update(one_chip):
    """Why ``_ring_write`` sorts: left to the compiler, 65,536 updates
    into ``u32[131,072,000]`` take the serial lowering (no sort, no flag,
    135,168 B of VMEM). The day this reads 16,359,424 the compiler windows
    such a ring by itself, and the sort and the flags can go."""
    n = 131_072_000
    text = jax.jit(
        lambda ring, slot, word: ring.at[slot].set(word, mode="drop"),
        donate_argnums=0).lower(
            *_ring_avals(one_chip, "uint32", n, "int64")).compile().as_text()
    fusion, defined = _ring_write_fusion(text, f"u32[{n}]")
    assert _donated_leaf(defined, fusion) == (f"u32[{n}]", "0")
    assert " sort(" not in text and "indices_are_sorted=true" not in text
    assert _scoped_vmem(fusion) == [_SERIAL_VMEM]


_TUMBLING = """
@app:playback
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.timeBatch(1 sec)
select symbol, count() as n, min(price) as lo, max(price) as hi
group by symbol
insert into OutStream;
"""


def _tumbling_steps(keys, batch):
    """(data step, TIMER step) of ``BASELINE.json`` configs[2] under the
    engine's defaults: one warm batch that holds every key, one more a
    window later, whose clock advance fires the flush's TIMER chunk."""
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(_TUMBLING)
    seen = _spy_steps(rt.query_runtimes["bench"])
    symbols = np.array([f"S{i}" for i in range(keys)], dtype=object)
    for ts in (10_000, 11_000):
        rt.get_input_handler("StockStream").send_columns(
            {"symbol": symbols[np.arange(batch) % keys],
             "price": np.ones(batch, np.float32),
             "volume": np.ones(batch, np.int64)},
            timestamps=np.full(batch, ts, np.int64))
    manager.shutdown()
    data, timer, _data = seen
    return data, timer


# (e) BASELINE.json configs[2], the tumbling window folded into keys-wide
# accumulators (ops/tumbling_agg.py): the data step and the one-row TIMER
# step that flushes. State and output are [K] whatever the batch; every
# batch-wide scatter is ONE 32-bit operand (the chip's sorted path).
@pytest.mark.parametrize("keys,batch", [
    (1_000, 2_048),
    pytest.param(10_000, 65_536, marks=pytest.mark.slow),
])
def test_tumbling_steps_compile(one_chip, keys, batch):
    for name, (step, avals) in zip(("data", "timer"),
                                   _tumbling_steps(keys, batch)):
        compiled, seconds = _compile_for(one_chip, step, avals)
        m = _report(f"E timeBatch(1 sec) count/min/max {name} step "
                    f"B={batch} keys={keys}", compiled, seconds)
        widest = max(a.shape[0] for a in
                     jax.tree_util.tree_leaves(avals[0]["win"]) if a.shape)
        assert keys <= widest < 2 * keys + 16     # as wide as the keys
        assert m.output_size_in_bytes < 64 * widest * 8
        scatters = _scatters(compiled.as_text())
        if name == "data":
            assert scatters
        for scope, primitive, result in scatters:
            assert not result.startswith("("), (
                f"a 64-bit scatter in the tumbling step: "
                f"{scope}/{primitive} -> {result}")


# (b) phase C: the two-step NFA at K = 16,384 partition-key slots.
@pytest.mark.parametrize("keys,batch", [
    (10_000, 1_024),
    pytest.param(10_000, 16_384, marks=pytest.mark.slow),
])
def test_nfa_steps_compile(one_chip, keys, batch):
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(_PATTERN)
    steps = rt.query_runtimes["nfa"]._steps = _SpyingSteps()
    names = np.array([f"K{i}" for i in range(keys)], dtype=object)
    # one chunk more than covers the keys: a step enters ``_steps`` on its
    # first call and is looked up there (and seen) from its second
    for c0 in range(0, keys + batch, batch):
        k = names[(c0 + np.arange(batch)) % keys]
        ts = np.full(batch, 1_000 + c0, np.int64)   # monotone feed
        rt.get_input_handler("AStream").send_columns(
            {"k": k, "v": np.zeros(batch)}, timestamps=ts)
        rt.get_input_handler("BStream").send_columns(
            {"k": k, "v": np.ones(batch)}, timestamps=ts + 1)
    manager.shutdown()
    # the loop-free two-step kernel, not the serial engine
    assert sorted(steps.seen) == [("AStream", False), ("BStream", False)]
    for (stream, _generic), (step, avals) in sorted(steps.seen.items()):
        assert avals[0]["nfa"]["consumed"].shape[0] == 16_384
        # both steps carry, in the ONE program, their emitted rows
        # compacted to twice the batch (ops/compact.py) and, beside them,
        # padded ([batch x 33])
        out = step.lower(*avals).out_info[1]
        assert out["v1"].shape == (2 * batch,)
        assert {k: v.shape for k, v in out[PADDED_KEY].items()} == {
            k: (batch * 33,) for k in out if k not in (PADDED_KEY, "__meta__")}
        compiled, seconds = _compile_for(one_chip, step, avals)
        _report(f"C nfa {stream} step B={batch} K=16384", compiled, seconds)


# (b2) the sequence cell's query: the generic round engine (a
# ``while_loop`` in ``siddhi.nfa_rounds``, one iteration a tile of W rows
# of one round) at K = 16,384 and S = 32.
# what the compiler itself emits to carry a leaf through the loop (no
# op_name of the program's)
_STATE_MOVES = ("get-tuple-element", "copy-start", "copy-done",
                "custom-call", "slice-start", "slice-done", "bitcast")
_INSTR = re.compile(
    r'\s*(?:ROOT )?%[\w.\-]+ = (?:\w+)(\[[\d,]*\])\S* ([a-z][\w\-]*)\('
    r'(?:.*op_name="([^"]*)")?')


def _computation(hlo_text, name):
    """``(opcode, shape, op_name)`` of every array-valued instruction of
    one computation of a compiled program, layouts stripped."""
    lines = hlo_text.split(f"\n{name} ", 1)[1].split("\n}", 1)[0]
    return [(m[2], m[1], m[3] or "") for m in map(_INSTR.match,
                                                  lines.splitlines()[1:])
            if m]


@pytest.mark.parametrize("keys,batch", [
    (10_000, 1_024),
    pytest.param(10_000, 16_384, marks=pytest.mark.slow),
])
def test_sequence_step_compiles(one_chip, keys, batch):
    import json
    import os

    from siddhi_tpu.ops.nfa import _tile_rows

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "sequence_peak_10k.json"), encoding="utf-8") as f:
        app = json.load(f)["app"]
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(app)
    steps = rt.query_runtimes["seq"]._steps = _SpyingSteps()
    names = np.array([f"S{i}" for i in range(keys)], dtype=object)
    for c0 in range(0, keys + batch, batch):
        k = names[(c0 + np.arange(batch)) % keys]
        rt.get_input_handler("Stream1").send_columns(
            {"symbol": k, "price": 30.0 + np.arange(batch) % 3.0,
             "volume": np.ones(batch, np.int32)},
            timestamps=c0 + np.arange(batch, dtype=np.int64))
    manager.shutdown()
    assert sorted(steps.seen) == [("Stream1", True)]   # the generic engine
    step, avals = steps.seen[("Stream1", True)]
    assert avals[0]["nfa"]["active"].shape == (16_384, 32)
    compiled, seconds = _compile_for(one_chip, step, avals)
    _report(f"sequence step B={batch} K=16384", compiled, seconds)
    text = compiled.as_text()
    assert " while(" in text and "siddhi.nfa_rounds" in text
    # the loop works on a tile of W rows, never on the whole batch: no
    # result in its body is [B, S] or [B, S + 1] but the [K, S] state's
    # own carriage and write-back (at B = K the shapes are alike)
    body = _computation(text, re.search(r" while\(.*?body=(%[\w.\-]+)",
                                        text)[1])
    W = _tile_rows(batch, 16_384)
    assert W < batch and [r for r in body if r[1].startswith(f"[{W},")]
    whole = [r for r in body if re.match(rf"\[{batch},3[23]\]", r[1])]
    assert all((op in _STATE_MOVES and not name) or (
        op == "fusion" and name.endswith("/scatter"))
        for op, _shape, name in whole), whole


# (d) the routed path on a mesh of the four described chips.
def _mesh4(topo):
    from siddhi_tpu.parallel.mesh import KEY_AXIS

    return Mesh(np.asarray(topo.devices[:4]), (KEY_AXIS,))


_COLLECTIVE = re.compile(
    r" (all-to-all|all-gather|all-reduce|collective-permute)(?:-start)?\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _collectives(hlo_text):
    """``(opcode, op_name)`` of every collective of a compiled program
    (the ``-start`` half of an asynchronous pair stands for the pair)."""
    found = []
    for line in hlo_text.splitlines():
        op = _COLLECTIVE.search(line)
        if op:
            name = _OP_NAME.search(line)
            found.append((op.group(1), name.group(1) if name else ""))
    return found


# the small shape is tier-1; the slow all_to_all case is the cell
# ``partition_len1k_40k.hot20_bulk_x4`` of BENCHMARK.json as the chip
# compiles it: 40,000 keys (16,384 a chip), 262,144-row batches, 81,920
# rows a shard, per-pair quota 20,480
@pytest.mark.parametrize("exchange,marker,window,keys,batch,rows_per_shard", [
    ("all_to_all", "all-to-all", 100, 1_000, 4_096, 4_096),
    pytest.param("all_to_all", "all-to-all", 1_000, 40_000, 262_144, 81_920,
                 marks=pytest.mark.slow),
])
def test_device_routed_step_compiles(topo, exchange, marker, window, keys,
                                     batch, rows_per_shard):
    """The whole ``device_route_query_step`` body on the described mesh:
    install routing on a CPU mesh of the same width to size the layout,
    then lower the routed program for the four described chips."""
    from siddhi_tpu.parallel import mesh as M

    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(_STOCK.format(
        precision="fast", W=window, price="float", group="", tail="end;",
        head="partition with (symbol of StockStream)\nbegin"))
    rt.start()
    q = rt.query_runtimes["bench"]
    M.device_route_query_step(q, M.make_mesh(4),
                              rows_per_shard=rows_per_shard,
                              exchange="all_to_all")
    seen = _spy_steps(q)
    symbols = np.array([f"S{i}" for i in range(keys)], dtype=object)
    rt.get_input_handler("StockStream").send_columns(
        {"symbol": symbols[np.arange(batch) % keys],
         "price": np.ones(batch, np.float32),
         "volume": np.ones(batch, np.int64)},
        timestamps=np.zeros(batch, np.int64))
    _step, (state, cols, now) = seen[-1]
    luts = _avals(q._route_layout.device_luts())
    assert q._route_layout.quota == rows_per_shard // 4
    assert q._route_layout.localK >= keys // 4
    # the same layout and body over the described chips: shard_map takes
    # its devices from the mesh, so plain avals are enough
    q._route_layout.mesh = _mesh4(topo)
    routed = M.routed_step_for(q)._routed_raw
    manager.shutdown()
    t0 = time.perf_counter()
    lowered = routed.lower(state, cols, luts, now)
    compiled = lowered.compile()
    _report(f"device-routed step n=4 {exchange} B={batch} keys={keys} "
            f"rows_per_shard={rows_per_shard}", compiled,
            time.perf_counter() - t0)
    text = compiled.as_text()
    assert marker in text
    # ingress and egress are traced in their own scopes, and nothing
    # crosses chips outside them: the benchmark's ``step_route_ms`` and
    # ``step_merge_ms`` read the scopes off the device trace
    names = _OP_NAME.findall(text)
    for scope in ("siddhi.route", "siddhi.merge", "siddhi.state",
                  "siddhi.select"):
        assert any(f"/{scope}/" in name for name in names), scope
    collectives = _collectives(text)
    assert {"all-gather", "all-reduce"} <= {op for op, _ in collectives}
    for op, name in collectives:
        assert "/siddhi.route/" in name or "/siddhi.merge/" in name, (
            f"{op} outside the routed step's scopes: {name!r}")
    # the egress moves each shard's OWN rows: of the row-wide columns only
    # the order keys (two u32 planes here) and the double that rides their
    # sort (``avgPrice``: a pair of f32 here) are all-gathered, nothing is
    # permuted by a gather, and every scatter writes ONE 32-bit plane
    # (``tests/test_mesh_routing.py::test_egress_moves_own_rows_only``
    # says the same of the lowered program, on the CPU)
    wide = re.compile(
        r"= (\(.*?\)|\S+) all-gather(?:-start)?\(.*/siddhi\.merge/all_gather")
    gathered = [m[1] for m in map(wide.search, text.splitlines()) if m]
    n_l = lowered.out_info[1]["__valid__"].shape[0]       # n * L
    planes = re.findall(rf"(\w+)\[{n_l}\]", " ".join(gathered))
    assert sorted(planes) == ["f32", "f32", "u32", "u32"], gathered
    assert not [name for name in names
                if name.endswith("/siddhi.merge/gather")]
    merge_scatters = [s for s in _scatters(text) if s[0] == "siddhi.merge"]
    assert len(merge_scatters) >= 8
    assert {result for _s, _p, result in merge_scatters} == {
        f"s32[{n_l}]"}, merge_scatters
    # ingress: the exchange buckets the three int64 columns (``__ts__``,
    # ``volume``, the row index) as two u32 words each, 66 ns an update
    # as one two-plane scatter (PERF.md section 5); the shard's ring step
    # writes its int64 columns the same way
    buckets = [result for scope, _p, result in _scatter_ops(text)
               if scope == "siddhi.route"]
    assert not [r for r in buckets if r.startswith("(")], buckets
    assert buckets.count(f"u32[{rows_per_shard}]") == 6, buckets
    ring_rows = max(a.shape[0] for a in
                    jax.tree_util.tree_leaves(state["win"])) // 4
    _assert_int64_rings_are_word_leaves(text, ring_rows)


# ------------------------------------------------------------------------
# The step programs of the benchmark's cells, by what JAX's compile cache
# keys them with: the StableHLO without debug info (scopes and locations
# are metadata and not in it). A PR that means to leave a cell's program
# alone (PR 33: a new scope, a new growth path) proves it here; a PR that
# means to change one replaces that cell's digests with what this test
# prints (PR 32 printed the first five: PERF.md section 6; PR 34 changed
# the keyed ring's layout and PR 36 how its writes are handed their slots,
# so cells 1, 4 and 6, and only they, were new each time; the NFA steps'
# meta gained the generic engine's round count, so cell 3's two are new
# beside the sequence cell's one; the generic engine's tiles changed the
# sequence cell's alone, the tile count riding the round count's lane).
_STEP_SHA256 = {
    "partition_len1k_10k.hot20_bulk": [
        "86d87d364886479770fdb83acfff6b632f3dacf2d01408b251fd6735f26b8ef5"],
    "groupby_len1k_10k.uniform_bulk": [
        "7a62b53a00ec4f8fb6467bcbe998ae7b46666ea6f20ddf5ad627fcdc40ccafe5"],
    "pattern_ab_10k.rounds_bulk": [
        "11d9db3fc361df5d1f8ea954cf72229d348da1110a11b0e5fe75813abc75c7d7",
        "301ba1d07cb4aaebf8ac69d9d09344a99e914cb640b7bd53ae6b73e833fe103a"],
    "partition_len1k_40k.hot20_bulk_x4": [
        "df738d0bcbb69dd9e145d99e09d81ad42167bdda9d6fcf1c87015b04a09bd180"],
    "timebatch_1s_10k.hot20_tick250": [
        "a055e7ea31b399f49b871ee11dec9001f9402c7dfb5695fb840daf5a3282ce43",
        "a46ee91e0411de179849c770668c3f573c42a301e26b436dd40114fe56361f27"],
    "partition_len1k_100k.hot20_bulk_100k": [       # two key capacities
        "2d061f2d72cb319f4de50dd40f4c8a0075df794be5aa10e3098f3278f9ee98c1",
        "3230499bcb4279486e82f49244eb532341a659110469bac57244cd01f07dba8c"],
    "sequence_peak_10k.hot20_walk16k": [
        "01a0cca0e1769fa2030bbffba7782bfe6199e3935edafcfa881ba1d1c0028fac"],
}


@pytest.mark.parametrize("cell", list(_STEP_SHA256))
def test_a_cells_step_programs_are_the_ones_written_down(cell, monkeypatch):
    """The cell's rehearsal (its configuration's and traffic's
    ``rehearsal`` sizes, seed 11, the warm batch, the fill and four
    batches more, as ``benchmarks/run.py`` drives them): every distinct
    program its steps were called with, lowered again from the jitted
    callable and the arguments' shapes."""
    import hashlib

    from benchmarks import drive, generator, manifest
    from siddhi_tpu.observability.telemetry import InstrumentedJit

    seen = {}
    call = InstrumentedJit.__call__

    def spy(self, *args):
        avals = _avals(args)
        seen[(self._key, str(avals))] = (self._fn, avals)
        return call(self, *args)

    monkeypatch.setattr(InstrumentedJit, "__call__", spy)
    found = manifest.Cell(cell)
    sizes, traffic = found.sized(True)
    feed = generator.make_feed(found.config, sizes, traffic, 11)
    manager, rt, _collector = drive.build_app(found.config, sizes,
                                              found.chips)
    sender = drive.Sender(rt, feed)
    for i in range(len(feed.warm) + feed.fill_batches + 4):
        sender.send(i)
    manager.shutdown()
    digests = sorted(
        hashlib.sha256(fn.lower(*avals).as_text().encode()).hexdigest()
        for fn, avals in seen.values())
    print(f"\n[step-sha256] {cell}: {digests}")
    assert digests == _STEP_SHA256[cell]
