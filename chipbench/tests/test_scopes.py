"""Device time by the program's named phases (chipbench/scopes.py): on
hand-made records whose answers are known, on the recorded chip traces
without op names, and on one recorded with them
(``zoo-q05b.b8s64.scoped.json``, written by ``chipbench/phases.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import phases, scopes
from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6
SCOPED = "zoo-q05b.b8s64.scoped.json"


def _rec(events, spans=(), host=()):
    """One chip; ``events`` are (name, start ms, length ms, op_name)."""
    return {"spans": [("chipbench.window", 0, 100 * MS), *spans],
            "devices": {"/device:TPU:0": [(n, s * MS, d * MS)
                                          for n, s, d, _ in events]},
            "op_names": {"/device:TPU:0": [op for *_, op in events]},
            "host": list(host)}


def test_each_op_counts_under_its_innermost_phase():
    rec = _rec([("fusion.1", 0, 10, "jit(step)/zo_perturb/add"),
                ("fusion.2", 10, 5,
                 "jit(step)/zo_update/jvp(server_forward)/dot_general"),
                ("fusion.3", 20, 5, "jit(step)/zo_update/sub"),
                ("copy.1", 30, 8, "jit(step)/copy"),
                ("fusion.4", 40, 2, "")])
    got = scopes.reduce(rec)["scope_s"]
    assert got == pytest.approx({"zo_perturb": 0.010,
                                 "server_forward": 0.005,
                                 "zo_update": 0.005,
                                 scopes.UNSCOPED: 0.010})


def test_within_counts_each_op_under_every_name_in_its_path():
    rec = _rec([("fusion.1", 0, 10, "jit(step)/zo_perturb/add"),
                ("fusion.2", 10, 5,
                 "jit(step)/zo_update/jvp(server_forward)/dot_general"),
                ("fusion.3", 20, 5, "jit(step)/zo_update/sub"),
                ("copy.1", 30, 8, "jit(step)/copy"),
                ("fusion.4", 40, 2, "")])
    out = scopes.reduce(rec)
    within = out["within_s"]
    assert within["zo_update"] == pytest.approx(0.010)
    assert within["server_forward"] == pytest.approx(0.005)
    assert within["zo_perturb"] == pytest.approx(0.010)
    # every op with an op_name holds jit and step: each counted once
    assert within["jit"] == within["step"] == pytest.approx(0.028)
    for phase, t in out["scope_s"].items():
        if phase != scopes.UNSCOPED:
            assert within[phase] >= t - 1e-15


def test_a_while_loop_keeps_only_its_residual_unscoped():
    # the loop [0, 50] is inside a phase; its body ran two scoped ops
    loop = "%while.13 = (s32[], f32[8,250]) while((s32[], f32[8,250]) %t)"
    rec = _rec([(loop, 0, 50, "jit(f)/zo_update/while"),
                ("fusion.1", 10, 10, "jit(f)/while/body/batch_gather/gather"),
                ("fusion.2", 30, 10, "jit(f)/while/body/ring_buffer/dus"),
                ("after", 60, 10, "jit(f)/zo_update/sub")])
    got = scopes.reduce(rec)["scope_s"]
    assert got == pytest.approx({scopes.UNSCOPED: 0.030,
                                 "batch_gather": 0.010,
                                 "ring_buffer": 0.010,
                                 "zo_update": 0.010})


def test_phases_and_unscoped_add_up_to_busy_time():
    rec = _rec([("a", 5, 10, "jit(s)/party_forward/dot"),
                ("b", 20, 30, "jit(s)/ring_buffer/copy"),
                ("c", 60, 10, "jit(s)/copy"),
                ("late", 95, 10, "jit(s)/zo_update/sub")])  # clipped
    out = scopes.reduce(rec)
    assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"])
    assert out["scope_s"]["zo_update"] == pytest.approx(0.005)


def test_no_phase_named_gives_no_phases():
    # the parent program: op names without any of the phases
    rec = _rec([("a", 5, 10, "jit(s)/dot"), ("b", 20, 10, "")])
    assert scopes.reduce(rec)["scope_s"] == {}


def test_gaps_keep_their_labels_and_name_the_runtime_event():
    rec = _rec([("a", 0, 10, ""), ("b", 40, 10, ""), ("c", 60, 40, "")],
               spans=[("chipbench.readback", 8 * MS, 35 * MS)],
               host=[("PjRtLoadedExecutable::Execute", 5 * MS, 50 * MS),
                     ("TransferFromDevice", 20 * MS, 10 * MS),
                     ("Idle", 52 * MS, 2 * MS)])
    out = scopes.reduce(rec)
    assert [g[:2] for g in out["gap_events"]] == out["idle_gaps"]
    assert [g[2] for g in out["gap_events"]] == ["TransferFromDevice",
                                                 "PjRtLoadedExecutable::"
                                                 "Execute"]


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")
                                        if p.name != SCOPED))
def test_records_without_op_names_reduce_as_before(name):
    rec = json.loads((DATA / name).read_text())
    out = scopes.reduce(rec)
    assert out.pop("scope_s") == {}
    assert out.pop("within_s") == {}
    gaps = out.pop("gap_events")
    assert out == tr.reduce(rec)
    assert [g[:2] for g in gaps] == out["idle_gaps"]
    assert all(g[2] == "-" for g in gaps)


def test_recorded_scoped_trace_phases_add_up_to_busy():
    rec = json.loads((DATA / SCOPED).read_text())
    out = scopes.reduce(rec)
    assert out["scope_s"], "the recorded trace names no phase"
    assert set(out["scope_s"]) <= set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"],
                                                         rel=0.01)
    # trimmed to phases, the record reduces to the same phases again
    again = scopes.reduce(phases.trim(rec, 1e9))
    assert again["scope_s"] == pytest.approx(out["scope_s"])
    assert again["idle_gaps"] == out["idle_gaps"]


def test_recorded_scoped_trace_within_matches_phases():
    # the record keeps each op's phase alone, so no phase nests in
    # another there: a phase's within_s is its scope_s, plus the own
    # time of the while ops in it, which scope_s counts as unscoped
    rec = json.loads((DATA / SCOPED).read_text())
    out = scopes.reduce(rec)
    w0, w1 = scopes._window(rec)
    loops = {}
    for plane, events in rec["devices"].items():
        inside = [((n, op), max(s, w0), min(s + d, w1)) for (n, s, d), op
                  in zip(events, rec["op_names"][plane])
                  if s < w1 and s + d > w0]
        for (n, op), _, _, own in tr._self_times(inside):
            if scopes.WHILE.search(n):
                loops[op] = loops.get(op, 0.0) + own / 1e9
    for phase in scopes.SCOPES:
        got = out["within_s"].get(phase, 0.0)
        assert got >= out["scope_s"].get(phase, 0.0) - 1e-12
        assert got == pytest.approx(out["scope_s"].get(phase, 0.0)
                                    + loops.get(phase, 0.0), abs=1e-12)
    assert set(out["within_s"]) >= set(out["scope_s"]) - {scopes.UNSCOPED}


def test_op_names_follow_the_program_that_ran_them():
    step = """HloModule jit_step, is_scheduled=true
  %copy.1 = f32[4]{0} copy(%p), metadata={op_name="jit(step)/ring_buffer/copy"}
  ROOT %fusion.2 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%f
"""
    programs = {}
    scopes.add_program(programs, "jit_step", step)
    scopes.add_program(programs, "jit_gather",
                       "  ROOT %copy.1 = s32[8]{0} copy(%q)\n")
    ops = [("%copy.1 = f32[4]{0} copy(f32[4]{0} %p)", 10, 1),
           ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %copy.1)", 12, 1),
           ("%copy.1 = s32[8]{0} copy(s32[8]{0} %q)", 30, 1),
           ("%copy.1 = s32[8]{0} copy(s32[8]{0} %q)", 50, 1)]
    modules = [("jit_step(1234)", 5, 10), ("jit_gather(99)", 28, 5)]
    assert scopes.op_names_in(ops, modules, programs) == [
        "jit(step)/ring_buffer/copy", "", "", ""]


def test_a_program_met_twice_keeps_what_both_agree_on():
    programs = {}
    scopes.add_program(programs, "jit_f", '  %a = f32[] add(), metadata='
                       '{op_name="x/zo_update/add"}\n  %b = f32[] neg()\n')
    scopes.add_program(programs, "jit_f", '  %a = f32[] add(), metadata='
                       '{op_name="x/zo_update/add"}\n  %b = f32[] neg(), '
                       'metadata={op_name="x/zo_perturb/neg"}\n')
    assert programs == {"jit_f": {"a": "x/zo_update/add"}}


def test_record_programs_keeps_each_compiled_programs_op_names(monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax._src import compiler
    monkeypatch.setattr(compiler, "compile_or_get_cached",
                        compiler.compile_or_get_cached)
    programs = {}
    phases.record_programs(programs)

    @jax.jit
    def f(x):
        with jax.named_scope("zo_update"):
            return jnp.sin(x) * 3.0

    f(jnp.arange(5.0)).block_until_ready()
    assert "zo_update" in {scopes.scope_of(op)
                           for op in programs["jit_f"].values()}


def test_per_round_divides_by_the_rounds():
    out = phases.per_round({"scope_s": {"zo_update": 0.004,
                                        scopes.UNSCOPED: 0.002},
                            "busy_s": 0.006, "gap_events": []}, 2)
    assert out["phases_ms"] == pytest.approx({"zo_update": 2.0,
                                              scopes.UNSCOPED: 1.0})
    assert out["sum_ms"] == pytest.approx(out["busy_ms"]) == 3.0
