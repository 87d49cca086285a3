"""The split of the round by scope and span (``chipbench/scopes.py``),
on a short HLO text and small synthetic traces, and on a CPU profile."""
import glob
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes, trace
from chipbench.trace import Event

MS = 1_000_000  # ns
DEV = "/device:TPU:0"
READERS = ("sim.local_phase_ms", "sim.eval_ms", "sim.coordinator_ms",
           "sim.trainer_idle_ms")

HLO = """\
HloModule jit_swarm_round, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0)
}

%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%p.1), index=1
  %copy.3 = f32[8]{0} copy(%get-tuple-element.1)
  %fusion.2 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation, \
metadata={op_name="jit(swarm_round)/bso.local_phase/while/body/transpose(jvp(fire))/mul" \
source_file="engine.py" source_line=3}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %fusion.2)
}

%fused_sub (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %subtract.1 = f32[8]{0} subtract(%param_0.2, %param_0.2)
}

%cond.1 (p.2: (s32[], f32[8])) -> pred[] {
  %p.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

%fused_eval (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0.1), \
metadata={op_name="jit(swarm_round)/bso.eval/neg"}
}

ENTRY %main.9 (a.1: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0), metadata={op_name="state.params"}
  %tuple.0 = (s32[], f32[8]{0}) tuple(%a.1, %a.1)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.1, body=%body.1, \
metadata={op_name="jit(swarm_round)/bso.local_phase/while"}
  %fusion.5 = f32[8]{0} fusion(%a.1), kind=kLoop, calls=%fused_eval
  %custom-call.6 = f32[8]{0} custom-call(%a.1), custom_call_target="tpu_custom_call", \
metadata={op_name="jit(swarm_round)/bso.stat_upload/jit(_swarm_features)/param_stats"}
  %copy.7 = f32[8]{0} copy(%a.1), metadata={op_name="data.val[\\'images\\']"}
  ROOT %fusion.8 = f32[8]{0} fusion(%a.1), kind=kLoop, calls=%fused_sub, \
metadata={op_type="x" op_name="jit(swarm_round)/bso.brain_storm/bso.kmeans/sub"}
}
"""


def op(name, start_ms, dur_ms):
    return Event(f"%{name} = f32[8]{{0}} op(...)", start_ms * MS, dur_ms * MS)


def test_scope_is_the_innermost_known_one():
    assert scopes.scope_of("jit(f)/bso.local_phase/while/body/"
                           "transpose(jvp(bso.eval))/mul") == "bso.eval"
    assert scopes.scope_of("jit(f)/bso.evaluate/bso.eq2x/mul") is None
    assert scopes.scope_of("data.val['images']") is None


def test_op_scopes_inherit_through_while_body_and_fusion():
    m = scopes.op_scopes(HLO)
    assert m["while.4"] == "bso.local_phase"
    # a while body's ops without a scope take the while's
    assert m["copy.3"] == m["tuple.1"] == "bso.local_phase"
    assert m["fusion.2"] == "bso.local_phase"
    # a fused computation's ops take their fusion's
    assert m["multiply.1"] == "bso.local_phase"
    assert m["subtract.1"] == "bso.kmeans"
    # a fusion with no metadata has no scope; its fused op keeps its own
    assert m["fusion.5"] is None and m["negate.1"] == "bso.eval"
    assert m["custom-call.6"] == "bso.stat_upload"
    assert m["fusion.8"] == "bso.kmeans"
    assert m["copy.7"] is None and m["a.1"] is None


def synthetic():
    """Two runs of the round program, [4, 46] and [50, 80] ms, under two
    ``bso.round`` spans, and a program of another module [85, 86] ms
    whose op shares a name with a scoped op of the round."""
    maps = {"jit_swarm_round(1)": scopes.op_scopes(HLO),
            "jit_copy(2)": {"copy.3": None}}
    modules = {DEV: [Event("jit_swarm_round(1)", 4 * MS, 42 * MS),
                     Event("jit_swarm_round(1)", 50 * MS, 30 * MS),
                     Event("jit_copy(2)", 85 * MS, 1 * MS)]}
    ops = [op("while.4", 5, 40), op("copy.3", 10, 5), op("fusion.2", 15, 25),
           op("fusion.5", 50, 10), op("custom-call.6", 60, 2),
           op("copy.7", 62, 8), op("fusion.8", 70, 2), op("copy.3", 85, 1)]
    host = [Event(trace.WINDOW_SPAN, 0, 100 * MS),
            Event(scopes.ROUND_SPAN, 0, 48 * MS),
            Event(scopes.DISPATCH_SPAN, 1 * MS, 2 * MS),
            Event(scopes.LOG_SPAN, 3 * MS, 44 * MS),
            Event(scopes.ROUND_SPAN, 48 * MS, 47 * MS),
            Event(scopes.DISPATCH_SPAN, 48 * MS, 1 * MS),
            Event(scopes.LOG_SPAN, 49 * MS, 41 * MS)]
    return trace.window({DEV: ops}, host), maps, modules


def test_self_time_by_scope():
    tr, maps, modules = synthetic()
    got = scopes.scope_seconds(tr, maps, modules)
    # the while's own 10 ms, its body's copy 5 ms and fusion 25 ms
    assert got["bso.local_phase"] == pytest.approx(0.040)
    assert got["bso.stat_upload"] == pytest.approx(0.002)
    assert got["bso.kmeans"] == pytest.approx(0.002)
    # fusion.5 (no metadata), copy.7 (an argument's name) and the other
    # module's copy.3
    assert got[scopes.OTHER] == pytest.approx(0.019)
    assert "bso.eval" not in got
    assert sum(got.values()) == pytest.approx(trace.mean_busy_s(tr))


def test_ops_outside_any_module_are_other():
    tr, maps, _ = synthetic()
    got = scopes.scope_seconds(tr, maps, {})
    assert got == {scopes.OTHER: pytest.approx(trace.mean_busy_s(tr))}


def test_idle_clipped_to_round_spans():
    tr, _, _ = synthetic()
    # device idle: [0, 5], [45, 50], [72, 85], [86, 100]; the round
    # spans [0, 95] hold 5 + 5 + 13 + 9 ms of it
    assert scopes.idle_in_spans(tr, scopes.ROUND_SPAN) == pytest.approx(0.032)
    assert scopes.idle_in_spans(tr, "no such span") == 0.0


def test_clock_offsets_of_each_round():
    tr, _, modules = synthetic()
    rounds = [m for m in modules[DEV] if m.name.startswith("jit_swarm")]
    assert scopes.clock_offsets(tr.host, rounds) == [
        (pytest.approx(3.0), pytest.approx(1.0)),
        (pytest.approx(2.0), pytest.approx(10.0))]
    # a device clock 5 ms early pairs each round with its own module
    # still, and the offsets say the clocks disagree
    early = [m._replace(start_ns=m.start_ns - 5 * MS) for m in rounds]
    assert scopes.clock_offsets(tr.host, early) == [
        (pytest.approx(-2.0), pytest.approx(6.0)),
        (pytest.approx(-3.0), pytest.approx(15.0))]


def load_reader(name):
    path = Path(scopes.__file__).parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", READERS)
def test_reader_without_device_plane_reads_nothing(name):
    ctx = SimpleNamespace(trace=trace.window({}, [
        Event(trace.WINDOW_SPAN, 0, 100 * MS)]), rounds=6)
    assert load_reader(name).read(ctx) is None


@pytest.mark.parametrize("scoped", [True, False])
def test_programs_from_a_cpu_profile(tmp_path, scoped):
    """The profiler records each program's HLO in the trace; where the
    programs that ran carry none of the scopes (as before they were
    added), the window splits into nothing."""
    def body(c, _):
        return jnp.tanh(c @ c), None

    def scoped_round(x):
        with jax.named_scope("bso.local_phase"):
            return jax.lax.scan(body, x, None, length=3)[0]

    def plain_round(x):
        return jax.lax.scan(body, x, None, length=3)[0]

    g = jax.jit(scoped_round if scoped else plain_round)
    g(jnp.ones((4, 4))).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            g(jnp.ones((4, 4))).block_until_ready()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = trace.load(str(tmp_path)).host
    start = next(e for e in host if e.name == trace.WINDOW_SPAN).start_ns
    tr = trace.Trace({DEV: []}, host, start, start + 1000)
    maps, modules = scopes.load_programs(path, tr)
    assert modules == {}                    # no TPU plane on the CPU
    name, = [n for n in maps if n.startswith(f"jit_{g.__name__}(")]
    ran = {DEV: [Event(name, start, 1000)]}
    whiles = [i for i in maps[name] if i.startswith("while")]
    assert {maps[name][i] for i in whiles} == (
        {"bso.local_phase"} if scoped else {None})

    tr = tr._replace(devices={DEV: [op(whiles[0], 0, 0)._replace(
        start_ns=start, dur_ns=10)]})
    split = scopes._split(tr, (maps, ran), rounds=1)
    if scoped:
        assert split["bso.local_phase"] == pytest.approx(1e-5)
        assert split["idle_ms"] == pytest.approx(0.99e-3)
        assert split["trainer_idle_ms"] is None
    else:
        assert split is None
    assert scopes.load_programs(path, tr._replace(start_ns=start + 1)) is None
