"""The split of the device's busy time by the program's scopes, on a
hand-built trace with known self times; the five readers over it; their
entries in BENCHMARK.json; and the loader on a hand-built `.xplane.pb`."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import program_scopes  # noqa: E402
from benchmarks.program_scopes import ByScope, Instruction, Op  # noqa: E402
from benchmarks.run import load_reader  # noqa: E402

MS = 1e6  # nanoseconds
WINDOW = (10 * MS, 210 * MS)
ROUTED = "jit(fn)/sparkdl:moe.routed"
SIZED = ROUTED + "/cond/branch_0_fun"
FULL = ROUTED + "/cond/branch_1_fun/sparkdl:moe.worst_case"
PASS = FULL + "/while/body/closed_call"

#: one program: a layer of latent attention, a shared expert, a routed
#: path that takes the sized arm once and the worst-case arm (a scan of
#: two passes) once, and a pooling that carries no scope
PROGRAM = {
    "fusion.q": Instruction("jit(fn)/sparkdl:mla.q/dot_general"),
    "flash_attention.7": Instruction("jit(fn)/sparkdl:mla.core/pallas_call"),
    # `o` with the next norm's sum fused in
    "fusion.o": Instruction(
        "jit(fn)/sparkdl:mla.out/dot_general",
        fused=("", "jit(fn)/sparkdl:mla.out/dot_general", "jit(fn)/sparkdl:mlp/reduce_sum"),
    ),
    # a weight fused in is named after the program's argument: no scope
    "fusion.mlp": Instruction(
        "jit(fn)/sparkdl:mlp/dot_general",
        fused=(
            "jit(fn)/sparkdl:mlp/dot_general", "jit(fn)/sparkdl:mlp/mul", "",
            "p['layers']['0']['mlp']['gate']",
        ),
    ),
    "sort.1": Instruction(ROUTED + "/sort"),
    "cond.3": Instruction(ROUTED + "/cond"),
    "gather.1": Instruction(SIZED + "/sparkdl:moe.gather/gather"),
    "grouped.1": Instruction(SIZED + "/sparkdl:moe.experts/pallas_call"),
    "while.2": Instruction(FULL + "/while"),
    "gather.2": Instruction(PASS + "/sparkdl:moe.gather/gather"),
    "grouped.2": Instruction(PASS + "/sparkdl:moe.experts/pallas_call"),
    "scatter.2": Instruction(PASS + "/sparkdl:moe.combine/scatter-add"),
    "reduce.9": Instruction("jit(fn)/reduce_sum"),
    "copy-start.1": Instruction(""),
}


def _op(name, start_ms, end_ms, program=7):
    return Op(program, name, start_ms * MS, (end_ms - start_ms) * MS)


def _ops():
    """Busy 20..126 and 130..190 and 205..210 of a window 10..210: 171 ms.
    `cond.3` runs twice: over 60..100 around the sized arm's two events
    (self 40 - 10 - 20 = 10) and over 130..190 around the loop `while.2`
    (135..185, two passes of three events with 4 ms of its own between
    them): the conditional keeps 10 ms there, the loop 50 - 46 = 4."""
    return [
        _op("fusion.q", 20, 30),
        _op("flash_attention.7", 30, 45),
        _op("fusion.o", 45, 50),
        _op("fusion.mlp", 50, 58),
        _op("sort.1", 58, 60),
        _op("cond.3", 60, 100),
        _op("gather.1", 62, 72),
        _op("grouped.1", 75, 95),
        _op("reduce.9", 100, 104),
        _op("copy-start.1", 104, 106),
        # an overlap without nesting: counted once, under the later event
        _op("fusion.q", 106, 120),
        _op("fusion.mlp", 116, 126),
        _op("cond.3", 130, 190),
        _op("while.2", 135, 185),
        _op("gather.2", 136, 140),
        _op("grouped.2", 140, 155),
        _op("scatter.2", 155, 159),
        _op("gather.2", 161, 165),
        _op("grouped.2", 165, 180),
        _op("scatter.2", 180, 184),
        # across the window's end, and outside it
        _op("fusion.mlp", 205, 230),
        _op("fusion.q", 0, 8),
        _op("fusion.q", 240, 250),
    ]


SELF_MS = {
    "mla.q": 10 + 10,
    "mla.core": 15,
    "mla.out": 5,
    "mlp": 8 + 10 + 5,
    "moe.routed": 2 + 10 + 10,
    "moe.gather": 10 + 4 + 4,
    "moe.experts": 20 + 15 + 15,
    "moe.combine": 4 + 4,
    "moe.worst_case": 4,
    "unscoped": 4 + 2,
}


def _found():
    return program_scopes.by_scope(_ops(), {7: PROGRAM}, WINDOW)


def test_self_time_by_innermost_scope_adds_up_to_busy():
    found = _found()
    assert found.busy_s == pytest.approx(0.171)
    assert found.self_s == {
        name: pytest.approx(ms / 1e3) for name, ms in SELF_MS.items()
    }
    assert sum(found.self_s.values()) == pytest.approx(found.busy_s)
    assert found.names("moe.") == [
        "moe.combine", "moe.experts", "moe.gather", "moe.routed", "moe.worst_case",
    ]


def test_an_envelope_keeps_its_time_less_what_it_contains():
    ops = [_op("cond.3", 60, 100), _op("gather.1", 62, 72), _op("grouped.1", 75, 95)]
    found = program_scopes.by_scope(ops, {7: PROGRAM}, WINDOW)
    assert found.busy_s == pytest.approx(0.040)
    assert found.self_s == {
        "moe.routed": pytest.approx(0.010),
        "moe.gather": pytest.approx(0.010),
        "moe.experts": pytest.approx(0.020),
    }


def test_a_scans_loop_and_the_nested_worst_case_arm():
    found = _found()
    # the loop's own time is the worst-case arm's self time; its passes'
    # events carry both outer scopes
    assert found.self_s["moe.worst_case"] == pytest.approx(0.004)
    assert found.any_s["moe.worst_case"] == pytest.approx(0.050)
    assert found.any_s["moe.routed"] == pytest.approx(0.002 + 0.040 + 0.060)
    assert found.any_s["moe.experts"] == found.self_s["moe.experts"]
    assert "unscoped" not in found.any_s


def test_a_fusion_of_two_scopes_is_counted_under_its_own_and_as_mixed():
    found = _found()
    assert found.mixed_s == {"mla.out": pytest.approx(0.005)}
    assert found.mixed_seconds("mla.q", "mla.out", "mlp") == pytest.approx(0.005)


def test_an_operation_under_none_is_named():
    found = _found()
    assert found.unscoped_ops == {
        "reduce": pytest.approx(0.004),
        "copy-start": pytest.approx(0.002),
    }
    # an event the program's text does not hold is under none too
    found = program_scopes.by_scope(
        _ops() + [_op("fusion.404", 192, 195)], {7: PROGRAM}, WINDOW
    )
    assert found.unscoped_ops["fusion"] == pytest.approx(0.003)
    assert found.busy_s == pytest.approx(0.174)


def test_an_overlap_without_nesting_is_counted_once_under_the_later_event():
    assert program_scopes.self_ns([(106, 120), (116, 126)]) == [10, 10]
    assert program_scopes.self_ns([(0, 10), (0, 4), (4, 10), (12, 13)]) == [0, 4, 6, 1]
    assert program_scopes.self_ns([]) == []


def test_programs_without_a_scope_give_nothing():
    bare = {
        name: Instruction(i.op_name.replace("sparkdl:", ""))
        for name, i in PROGRAM.items()
    }
    assert program_scopes.by_scope(_ops(), {7: bare}, WINDOW) is None
    # a scope in a program that did not run inside the window does not count
    assert program_scopes.by_scope(_ops(), {7: bare, 8: PROGRAM}, WINDOW) is None
    assert program_scopes.by_scope([], {7: PROGRAM}, WINDOW) is None


def test_the_sums_failure_raises():
    ok = ByScope(busy_s=1.0, self_s={"mlp": 0.6, "unscoped": 0.4004})
    assert ok.check() is ok
    with pytest.raises(ValueError, match="busy time"):
        ByScope(busy_s=1.0, self_s={"mlp": 0.6, "unscoped": 0.39}).check()


# -- the readers ----------------------------------------------------------------

COUNTERS = {
    "text.tokens": 30_000,
    "text.pad_tokens": 2_000,
    "mla.attention_tokens": 160_000,
    "moe.slots_held": 50_000,
    "moe.buffer_sized": 7,
    "moe.buffer_full": 1,
    "ssm.scan_tokens": 100_000,
}

JAMBA = {
    "fusion.in": Instruction("jit(fn)/sparkdl:mamba.in_proj/dot_general"),
    "fusion.conv": Instruction("jit(fn)/sparkdl:mamba.conv/mul"),
    "fusion.x": Instruction("jit(fn)/sparkdl:mamba.ssm_inputs/dot_general"),
    "selective_scan.3": Instruction("jit(fn)/sparkdl:mamba.scan/pallas_call"),
    "fusion.out": Instruction(
        "jit(fn)/sparkdl:mamba.out_proj/dot_general",
        fused=("jit(fn)/sparkdl:mamba.out_proj/dot_general", "jit(fn)/sparkdl:mlp/mul"),
    ),
    "fusion.mlp": PROGRAM["fusion.mlp"],
}


def _jamba_ops():
    return [
        _op("fusion.in", 20, 30, 9),
        _op("fusion.conv", 30, 36, 9),
        _op("fusion.x", 36, 38, 9),
        _op("selective_scan.3", 38, 50, 9),
        _op("fusion.out", 50, 57, 9),
        _op("fusion.mlp", 57, 77, 9),
    ]


def _ctx(tmp_path, counters=COUNTERS, traced=True):
    return {
        "trace": SimpleNamespace(window_s=0.200) if traced else None,
        "cell": SimpleNamespace(work_dir=str(tmp_path)),
        "counters": dict(counters),
        "chips": 1,
    }


@pytest.fixture
def loads(monkeypatch):
    """`load` stands in for a trace on disk; counts its calls."""
    calls = []

    def install(ops, programs):
        def load(trace_dir):
            calls.append(trace_dir)
            return ops, programs, WINDOW

        monkeypatch.setattr(program_scopes, "load", load)
        program_scopes.by_scope_of.cache_clear()
        return calls

    yield install
    program_scopes.by_scope_of.cache_clear()


#: reader -> (the counters it divides by, ms it reads of `_ops()`)
READERS = {
    "mlp.ms_per_ktoken": (("text.tokens", "text.pad_tokens"), 23),
    "mla.projection_ms_per_ktoken": (("mla.attention_tokens",), 20 + 5),
    "moe.routed_ms_per_kslot": (("moe.slots_held",), 102),
}


def test_the_readers_parse_one_trace_once_and_divide_as_stated(tmp_path, loads):
    calls = loads(_ops(), {7: PROGRAM})
    ctx = _ctx(tmp_path)
    got = {name: load_reader(name)(ctx) for name in READERS}
    got["program.unscoped_busy_pct"] = load_reader("program.unscoped_busy_pct")(ctx)
    assert calls == [os.path.join(str(tmp_path), "trace")]
    for name, (counters, ms) in READERS.items():
        count = sum(COUNTERS[c] for c in counters)
        assert got[name]["seconds"] == pytest.approx(ms / 1e3), name
        assert got[name]["value"] == pytest.approx(ms / (count / 1e3)), name
    assert got["mlp.ms_per_ktoken"]["mixed_seconds"] == 0
    assert got["mla.projection_ms_per_ktoken"]["mixed_seconds"] == pytest.approx(0.005)
    assert got["mla.projection_ms_per_ktoken"]["mla.q_s"] == pytest.approx(0.020)
    assert "mla.core_s" not in got["mla.projection_ms_per_ktoken"]
    routed = got["moe.routed_ms_per_kslot"]
    assert routed["own_s"] == pytest.approx(0.022)
    assert routed["moe.gather_s"] == pytest.approx(0.018)
    assert routed["moe.experts_s"] == pytest.approx(0.050)
    assert routed["moe.combine_s"] == pytest.approx(0.008)
    assert routed["worst_case_seconds"] == pytest.approx(0.050)
    # own + parts + the worst-case arm's own time: the whole
    assert routed["own_s"] + 0.018 + 0.050 + 0.008 + 0.004 == pytest.approx(0.102)
    assert (routed["moe.buffer_sized"], routed["moe.buffer_full"]) == (7, 1)


def test_the_table_beside_the_unscoped_share(tmp_path, loads):
    loads(_ops(), {7: PROGRAM})
    got = load_reader("program.unscoped_busy_pct")(_ctx(tmp_path))
    assert got["value"] == pytest.approx(100 * 6 / 171)
    assert got["seconds"] == pytest.approx(0.006)
    assert got["busy_seconds"] == pytest.approx(0.171)
    assert got["mixed_seconds"] == pytest.approx(0.005)
    scopes = {k[len("scope."):]: v for k, v in got.items() if k.startswith("scope.")}
    assert scopes == {
        n: pytest.approx(ms / 1e3) for n, ms in SELF_MS.items() if n != "unscoped"
    }
    assert got["seconds"] + sum(scopes.values()) == pytest.approx(got["busy_seconds"])
    # an outer scope's whole, only where it is more than its self time
    under = {k for k in got if k.startswith("under.")}
    assert under == {"under.moe.routed", "under.moe.worst_case"}
    assert got["under.moe.routed"] == pytest.approx(0.102)
    assert got["unscoped.reduce"] == pytest.approx(0.004)
    json.dumps(got)


def test_the_mixer_is_every_mamba_scope_but_the_scan(tmp_path, loads):
    loads(_jamba_ops(), {9: JAMBA})
    ctx = _ctx(tmp_path)
    got = load_reader("mamba.mixer_ms_per_ktoken")(ctx)
    assert got["seconds"] == pytest.approx(0.025)
    assert got["value"] == pytest.approx(25 / 100)
    assert got["mixed_seconds"] == pytest.approx(0.007)
    assert "mamba.scan_s" not in got and got["mamba.conv_s"] == pytest.approx(0.006)
    assert load_reader("mlp.ms_per_ktoken")(ctx)["value"] == pytest.approx(20 / 32)
    # a family without the scopes: nothing, whatever it counted
    assert load_reader("mla.projection_ms_per_ktoken")(ctx) is None
    assert load_reader("moe.routed_ms_per_kslot")(ctx) is None


ALL_READERS = [*READERS, "mamba.mixer_ms_per_ktoken", "program.unscoped_busy_pct"]


@pytest.mark.parametrize("name", ALL_READERS)
def test_reader_reports_nothing_without_scopes(tmp_path, loads, name):
    """The parent of the PR that brought the scopes: a trace whose
    programs name none. The reader returns nothing and does not raise."""
    bare = {n: Instruction("jit(fn)/dot_general") for n in {**PROGRAM, **JAMBA}}
    loads(_ops() + _jamba_ops(), {7: bare, 9: bare})
    assert load_reader(name)(_ctx(tmp_path)) is None


@pytest.mark.parametrize("name", ALL_READERS)
def test_reader_returns_none_for_a_rehearsal(tmp_path, name):
    """`--rehearse-cpu` has no trace, and nothing is looked for."""
    assert load_reader(name)(_ctx(tmp_path, traced=False)) is None


@pytest.mark.parametrize("name", ALL_READERS[:-1])
def test_reader_reports_nothing_without_its_counter(tmp_path, loads, name):
    loads(_ops() + _jamba_ops(), {7: PROGRAM, 9: JAMBA})
    assert load_reader(name)(_ctx(tmp_path, counters={})) is None


# -- BENCHMARK.json ---------------------------------------------------------------

_BERT, _JAMBA = "bert-base-embed", "jamba2-3b-embed-windows"
_V2, _V32 = "deepseek-v2-embed-windows", "deepseek-v3.2-exp-embed-long-docs"
#: The five entries in the order their PR entered them, each with the
#: cells it listed then. A later cell may join a list (a model that
#: carries the scope); an accepted cell may not drop out of one.
CELLS = {
    "program.unscoped_busy_pct": {_BERT, _JAMBA, _V2, _V32},
    "mlp.ms_per_ktoken": {_BERT, _JAMBA, _V2, _V32},
    "mla.projection_ms_per_ktoken": {_V2, _V32},
    "moe.routed_ms_per_kslot": {_V2, _V32},
    "mamba.mixer_ms_per_ktoken": {_JAMBA},
}


@pytest.mark.parametrize("name", CELLS)
def test_entry_has_its_file_and_lists_accepted_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_entry(json.load(f), name)


def check_entry(bench, name, root=ROOT):
    """A function of `bench` and the checkout's root, so that
    `test_benchmark_grows.py` can put a copy with a fifth configuration
    through it. Nothing here says where in `per_layer` an entry stands
    but after the one entered before it, nor how many cells it lists."""
    names = [m["name"] for m in bench["per_layer"]]
    assert names.count(name) == 1
    order = [n for n in names if n in CELLS]
    assert order == list(CELLS)
    entry = bench["per_layer"][names.index(name)]
    assert os.path.isfile(
        os.path.join(root, "benchmarks", "layer_metrics", f"{name}.py")
    )
    assert (entry["source"], entry["layer"], entry["moves"], entry["better"]) == (
        "device_trace", "Program", "rows_per_s", "lower",
    )
    cells = {w["name"] for w in bench["workloads"]}
    assert CELLS[name] <= set(entry["workloads"]) <= cells
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    # beside the kernel metric whose divisor it shares
    beside = {
        "mla.projection_ms_per_ktoken": "mla.attention_ms_per_ktoken",
        "moe.routed_ms_per_kslot": "moe.expert_ms_per_kslot",
        "mamba.mixer_ms_per_ktoken": "ssm.scan_ms_per_ktoken",
    }
    if name in beside:
        other = next(m for m in bench["per_layer"] if m["name"] == beside[name])
        assert set(other["workloads"]) == set(entry["workloads"])
        assert other["unit"] == entry["unit"]


# -- the loader -------------------------------------------------------------------


def test_load_joins_events_to_instructions_by_program_and_name(tmp_path):
    """A hand-built `.xplane.pb`: one device, one program's HLO on the
    metadata plane, a second program that did not run."""
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

    def hlo(name):
        proto = hlo_pb2.HloProto()
        proto.hlo_module.name = name
        fused = proto.hlo_module.computations.add(id=2, name="fused_computation")
        ins = fused.instructions.add(id=9, name="param_0", opcode="parameter")
        ins.metadata.op_name = "p['layers']['0']['attn']['o']"
        for i, op_name in enumerate(
            ["", "jit(fn)/sparkdl:mla.out/dot_general", "jit(fn)/sparkdl:mlp/reduce_sum"]
        ):
            ins = fused.instructions.add(id=10 + i, name=f"inner.{i}", opcode="add")
            ins.metadata.op_name = op_name
        main = proto.hlo_module.computations.add(id=1, name="main")
        ins = main.instructions.add(id=1, name="fusion.o", opcode="fusion")
        ins.metadata.op_name = "jit(fn)/sparkdl:mla.out/dot_general"
        ins.called_computation_ids.append(2)
        ins = main.instructions.add(id=2, name="cond.3", opcode="conditional")
        ins.metadata.op_name = ROUTED + "/cond"
        ins = main.instructions.add(id=3, name="copy.1", opcode="copy")
        return proto.SerializeToString()

    BIG = 13285704225790856756  # a program's id as the chip's trace gave one
    space = xplane_pb2.XSpace()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench:window"
    line = host.lines.add(name="main", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=int(9e9), duration_ps=int(200e9))

    device = space.planes.add(name="/device:TPU:0")
    device.stat_metadata[1].name = "program_id"
    texts = {
        1: "%fusion.o = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kOutput",
        2: "%cond.3 = (f32[8]{0}) conditional(s32[] %b, (f32[8]{0}) %t)",
        3: "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)",
    }
    for i, text in texts.items():
        md = device.event_metadata[i]
        md.id, md.name = i, text
        md.display_name = text.split(" = ")[0].lstrip("%") if i != 3 else ""
        md.stats.add(metadata_id=1, uint64_value=BIG)
    ops = device.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (start_ms, ms) in {1: (20, 5), 2: (30, 40), 3: (60, 2)}.items():
        ops.events.add(metadata_id=i, offset_ps=int(start_ms * 1e9), duration_ps=int(ms * 1e9))
    device.event_metadata[9].name = f"jit_fn({BIG})"
    device.lines.add(name="XLA Modules").events.add(
        metadata_id=9, offset_ps=0, duration_ps=int(300e9)
    )

    holder = space.planes.add(name="/host:metadata")
    holder.stat_metadata[1].name = "Hlo Proto"
    # the map's keys are signed: an id over 2**63 is kept as its negative
    for key in (BIG - 2**64, 78):
        md = holder.event_metadata[key]
        md.id, md.name = key, f"jit_fn({key % 2**64})"
        md.stats.add(metadata_id=1, bytes_value=hlo("jit_fn"))

    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(space.SerializeToString())

    got_ops, programs, window = program_scopes.load(str(tmp_path))
    assert window == (pytest.approx(1000 + 9 * MS), pytest.approx(1000 + 209 * MS))
    assert [(o.program, o.name) for o in got_ops] == [
        (BIG, "fusion.o"), (BIG, "cond.3"), (BIG, "copy.1"),
    ]
    assert got_ops[0].start_ns == pytest.approx(1000 + 20 * MS)
    assert got_ops[1].dur_ns == pytest.approx(40 * MS)
    assert set(programs) == {BIG}
    assert programs[BIG]["fusion.o"].fused == (
        "p['layers']['0']['attn']['o']", "",
        "jit(fn)/sparkdl:mla.out/dot_general", "jit(fn)/sparkdl:mlp/reduce_sum",
    )
    found = program_scopes.by_scope(got_ops, programs, window)
    assert found.self_s == {
        "mla.out": pytest.approx(0.005),
        "moe.routed": pytest.approx(0.038),
        "unscoped": pytest.approx(0.002),
    }
    assert found.mixed_s == {"mla.out": pytest.approx(0.005)}
    program_scopes.by_scope_of.cache_clear()
    assert program_scopes.by_scope_of(str(tmp_path)).busy_s == pytest.approx(0.045)
    program_scopes.by_scope_of.cache_clear()


def test_load_raises_without_a_trace(tmp_path):
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    with pytest.raises(FileNotFoundError, match="xplane"):
        program_scopes.load(str(tmp_path))
