"""CPU tests of `chipbench.spans` and `trace_spans.py`: the scope of each op
of a compiled program, the trainer's host spans against the chip's idle
time, what the scope and span metrics read on synthetic and recorded chip
traces, and a traced tiny run that keeps both. The readers the benchmark
already has read the committed trace as they always did."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)

import trace_spans  # noqa: E402
from chipbench import hlo, spans, spec, trace  # noqa: E402
from chipbench.readers import Context, load_reader  # noqa: E402
from test_bench_chip_run import CPU_PEAKS, tiny_cell  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_trace.json")
RECORDED_SPANS = os.path.join(HERE, "recorded_trace_spans.json")


def _ctx(tr, **kw):
    args = dict(trace=tr, config={}, traffic={}, chips=1, nodes=1, edges=1,
                peaks={})
    return Context(**dict(args, **kw))


def test_recorded_trace_readers_read_as_before():
    """The per-layer readers the benchmark has read the committed chip
    trace, which predates the program's scopes and spans, as they did
    before the program carried them."""
    with open(RECORDED) as f:
        recorded = trace.DeviceTrace.from_json(json.load(f))
    cell = spec.load_cell("reddit-sage.pipegcn-gf.1chip")
    ctx = _ctx(recorded, config=cell.config, traffic=cell.traffic,
               nodes=16384, edges=974490, peaks=spec.peaks_for("TPU v5 lite"))
    got = {n: load_reader(n).read(ctx) for n in (
        "spmm_ms", "dense_ms", "device_idle_pct", "step_mfu",
        "spmm_roofline")}
    assert got == {"spmm_ms": 316.2510865, "dense_ms": 0.4490865,
                   "device_idle_pct": 1.3521097583248376,
                   "step_mfu": 0.06792975156901129,
                   "spmm_roofline": 0.10595816285672786}
    # no scopes and no spans: what they would read is nothing
    st = spans.SpanTrace(recorded, {}, [])
    got = spans.summary(st)
    assert (got["exchange_ms"], got["update_ms"], got["host_stall_ms"]) == \
        (None, None, None)


# -- synthetic: two epochs of one chip ------------------------------------

def _op(name, start, dur, module="jit_step"):
    return trace.Op(0, name, module, float(start), float(dur))


def _span(name, start, dur, step=-1):
    return spans.Span(name, float(start), float(dur), step)


def _two_epochs() -> spans.SpanTrace:
    """One chip, two epochs of 10 ms: in each, the host splits the key
    (1 ms, chip idle), dispatches (1 ms, idle), and waits while the step
    runs 6 ms (exchange 1, aggregate 4, update 1); then its guard check
    (1 ms, idle) and 1 ms directly under the epoch span (idle)."""
    ops, host = [], []
    for e in range(2):
        t = e * 10e6
        host += [_span("trainer/epoch", t, 10e6, e),
                 _span("trainer/rng", t, 1e6),
                 _span("trainer/dispatch", t + 1e6, 1e6),
                 _span("trainer/wait", t + 2e6, 6e6),
                 _span("trainer/guard", t + 8e6, 1e6)]
        ops += [_op("fusion.1", t + 2e6, 1e6), _op("fusion.2", t + 3e6, 4e6),
                _op("fusion.3", t + 7e6, 1e6)]
    tr = trace.DeviceTrace(ops={0: ops}, modules={}, window_s=0.02,
                           epochs=2)
    return spans.SpanTrace(tr, {"jit_step": {"fusion.1": "exchange",
                                             "fusion.2": "aggregate",
                                             "fusion.3": "update"}}, host)


@pytest.mark.parametrize("scope,ms", [("exchange", 1.0), ("aggregate", 4.0),
                                      ("update", 1.0), ("loss", None)])
def test_scope_time_picks_its_scope(scope, ms):
    got = spans.scope_ms(_two_epochs(), scope)
    assert got == (pytest.approx(ms) if ms else None)


def test_host_stall_is_idle_time_under_child_spans_but_wait():
    """Idle per epoch: rng 1 ms + dispatch 1 ms + guard 1 ms are stalls;
    the 1 ms under the epoch span alone is not, nor is the wait."""
    st = _two_epochs()
    assert spans.idle_by_span(st, 0) == {
        "trainer/rng": 2e6, "trainer/dispatch": 2e6, "trainer/guard": 2e6,
        "trainer/epoch": 2e6}
    assert spans.host_stall_ms(st) == pytest.approx(3.0)
    # idle while the host waits on the chip is the chip's, not a stall
    st.trace.ops[0] = [o for o in st.trace.ops[0] if o.name != "fusion.3"]
    assert spans.idle_by_span(st, 0)["trainer/wait"] == 2e6
    assert spans.host_stall_ms(st) == pytest.approx(3.0)


def test_innermost_span_and_uncovered_idle():
    host = [_span("trainer/epoch", 0, 10), _span("trainer/eval", 2, 6),
            _span("trainer/log", 8, 1)]
    assert spans.innermost(host) == [(0, 2, "trainer/epoch"),
                                     (2, 8, "trainer/eval"),
                                     (8, 9, "trainer/log"),
                                     (9, 10, "trainer/epoch")]
    tr = trace.DeviceTrace(ops={0: [_op("a", 12, 3)]}, modules={},
                           window_s=1.0, epochs=1)
    st = spans.SpanTrace(tr, {}, host)
    # idle from the first span to the op, 10-12 under no span
    assert spans.idle(st, 0) == [(0, 12)]
    assert spans.idle_by_span(st, 0) == {"trainer/epoch": 3,
                                         "trainer/eval": 6,
                                         "trainer/log": 1, "": 2}
    assert spans.span_over(spans.innermost(host), 1, 9) == "trainer/eval"
    assert spans.span_over(spans.innermost(host), 10, 12) == ""


def test_span_trace_json_round_trip():
    st = _two_epochs()
    back = spans.SpanTrace.from_json(json.loads(json.dumps(st.to_json())))
    assert back.trace.ops == st.trace.ops and back.host == st.host
    assert back.scope(back.trace.ops[0][1]) == "aggregate"
    assert back.host[0].step_num == 0


def test_idle_gaps_are_named_by_the_span_over_them():
    st = _two_epochs()
    got = spans.summary(st)
    # the longest gap, between the epochs: guard 1 ms, epoch alone 1 ms,
    # rng 1 ms, dispatch 1 ms; ties go to the first span found
    assert got["idle_gaps"][0] == [
        "after fusion.3 before fusion.1 in trainer/guard",
        pytest.approx(4e-3)]
    assert got["idle_child_share"] == pytest.approx(6 / 8)
    assert got["scope_ms"] == pytest.approx(
        {"exchange": 1.0, "aggregate": 4.0, "update": 1.0})
    assert got["exchange_ms"] == pytest.approx(1.0)
    assert got["host_stall_ms"] == pytest.approx(3.0)
    # without spans the gaps keep the names of the ops around them
    st.host = []
    assert spans.summary(st)["idle_gaps"][0][0] == \
        "after fusion.3 before fusion.1"


_HLO = """HloModule jit_step, entry_computation_layout={()->f32[4]}

%fused_inner (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %s = f32[4]{0} sine(%p), metadata={op_name="jit(step)/fwd/L0/transform/vmap(aggregate)/sin"}
}

%fused_outer (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %f = f32[4]{0} fusion(%q), kind=kLoop, calls=%fused_inner
}

ENTRY %main () -> f32[4] {
  %c = f32[4]{0} constant({1, 2, 3, 4})
  %copy.1 = f32[4]{0} copy(%c)
  %fusion.7 = f32[4]{0} fusion(%copy.1), kind=kCustom, calls=%fused_outer
  %n = f32[4]{0} negate(%fusion.7), metadata={op_name="jit(step)/bwd/L1/exchange/update/neg"}
  ROOT %t = f32[4]{0} cosine(%n), metadata={op_name="jit(step)/cos"}
}
"""


def test_hlo_scope_rule():
    """A fusion without metadata takes its root's scope through nested
    fusions; a scope inside a transformation is unwrapped; the innermost
    scope wins; an instruction without metadata takes the scope of the
    one that reads it; an op outside every scope has none."""
    got = spans.scopes(_HLO)
    assert set(got) == set(hlo.facts(_HLO))
    assert got["fusion.7"] == "aggregate"
    assert got["n"] == "update"
    assert got["copy.1"] == "aggregate"
    assert got["t"] == ""
    assert spans.scope_of("jit(step)/transpose(jvp(exchange))/x") == \
        "exchange"
    assert spans.scope_of("fwd/L2/transformer/x") == ""


# -- a chip trace with the scopes and spans --------------------------------

@pytest.fixture(scope="module")
def recorded_spans():
    with open(RECORDED_SPANS) as f:
        return spans.SpanTrace.from_json(json.load(f))


def test_recorded_spans_attribution(recorded_spans):
    """Two epochs of a chip trace taken with the program's scopes and the
    trainer's spans, the second with the eval: each op counts toward at
    most one of the four time metrics, whose sum per epoch is at most the
    chip's busy time per epoch; every op of the train step carries a
    scope; the host stalls are at most the idle time per epoch."""
    st = recorded_spans
    tr = st.trace
    spmm, dense = load_reader("spmm_ms"), load_reader("dense_ms")
    picks = (spmm.is_aggregation, dense.is_dense,
             lambda o: st.scope(o) == "exchange",
             lambda o: st.scope(o) == "update")
    ops = tr.ops[tr.devices[0]]
    assert all(sum(p(o) for p in picks) <= 1 for o in ops)
    got = {n: load_reader(n).read(_ctx(tr)) for n in ("spmm_ms", "dense_ms")}
    got.update({k: v for k, v in spans.summary(st).items()
                if k.endswith("_ms") and k != "scope_ms"})
    assert all(v for v in got.values()), got
    busy_ms = trace.busy_ns(tr, tr.devices[0]) / 1e6 / tr.epochs
    idle_ms = tr.window_s * 1e3 / tr.epochs - busy_ms
    assert sum(got[n] for n in ("spmm_ms", "dense_ms", "exchange_ms",
                                "update_ms")) <= busy_ms
    assert 0 < got["host_stall_ms"] <= idle_ms
    assert all(st.scope(o) for o in ops if o.module == "jit_step")
    # every aggregation by spmm_ms's rule is in the aggregate scope
    assert all(st.scope(o) == "aggregate" for o in ops
               if spmm.is_aggregation(o))
    assert [s.step_num for s in st.host if s.name == "trainer/epoch"] == \
        [19, 20]
    assert any(s.name == "trainer/eval" for s in st.host)


# -- trace_spans.py -------------------------------------------------------

def test_trace_spans_refuses_a_host_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "trace_spans.py"), "--workload",
         "reddit-sage.pipegcn-gf.1chip", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr


def test_traced_run_keeps_the_scopes_and_the_trainer_spans():
    """A tiny traced run through `run.run_cell`: the result is the one
    `run.py` gives, and the scopes of its programs and the trainer's spans
    are kept, every epoch span of the window with its epoch as `step_num`
    from the first the profiler saw whole (the window opens inside epoch
    WARM_EPOCHS's dispatch). The harness is left as it was."""
    from chipbench import job, readers
    facts, load = hlo.facts, trace.load
    lines = []
    result, st = trace_spans.traced(
        tiny_cell(), seed=2**31 + 13, seconds=0.5,
        t_start=time.perf_counter(), out=lines.append, peaks=CPU_PEAKS)
    assert (hlo.facts, trace.load) == (facts, load)
    assert result["correct"], result["checks"]
    steps = [s.step_num for s in st.host if s.name == "trainer/epoch"]
    assert steps and steps[0] == job.WARM_EPOCHS + 1
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    assert {s.name for s in st.host} >= {"trainer/rng", "trainer/dispatch",
                                         "trainer/wait", "trainer/guard"}
    assert {"jit_step", "jit_eval_forward"} <= set(st.scopes)
    assert set(st.scopes["jit_step"].values()) == set(spans.SCOPES)
    # the CPU's trace has no TPU plane: no device op to read
    assert not st.trace.devices and result["spans"] == {}
    assert result["metrics"] == readers.read_all(
        [m["name"] for m in tiny_cell().per_layer], _ctx(st.trace))
