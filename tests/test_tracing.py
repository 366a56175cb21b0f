"""One clock for the program's spans and the device trace (PR 25): a span
is made in one place (``telemetry.span``) and carries one profiler
annotation while annotations are on; spans know their parent and their
thread; the slot scheduler's loop is spanned phase by phase and its flight
records carry the phases; the serve programs have names; compiles are laid
to the span they fell in; a request keeps a stamp per token.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, ServeConfig
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.serve.trace import REQUEST_TID_BASE, RequestTrace
from trlx_tpu.utils import profiling
from test_serve import tiny_config_dict


@pytest.fixture()
def session():
    s = telemetry.start()
    yield s
    telemetry.start()


# --------------------------------------------------------------------- #
# (a) one span, one annotation, one clock
# --------------------------------------------------------------------- #


class _CountingAnnotation:
    opened = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _CountingAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("annotations", [True, False])
@pytest.mark.parametrize("with_session", [True, False])
@pytest.mark.parametrize("entry", ["annotate", "span"])
def test_one_annotation_per_span(monkeypatch, entry, with_session,
                                 annotations):
    """Exactly one TraceAnnotation per span while annotations are on —
    through ``annotate`` or ``telemetry.span``, with or without a
    session — and none while they are off."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = []
    open_span = profiling.annotate if entry == "annotate" else telemetry.span
    if with_session:
        telemetry.start()
    else:
        telemetry.stop()
    profiling.set_annotations(annotations)
    try:
        with open_span("phase"):
            with open_span("inner"):
                pass
    finally:
        profiling.set_annotations(False)
        telemetry.start()
    assert _CountingAnnotation.opened == (
        ["phase", "inner"] if annotations else []
    )


def test_maybe_trace_switches_annotations_and_leaves_python_tracer_off(
        monkeypatch, tmp_path):
    seen = {}

    class FakeTrace:
        def __init__(self, log_dir, profiler_options=None):
            seen["dir"] = log_dir
            seen["python_tracer_level"] = profiler_options.python_tracer_level

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "trace", FakeTrace)
    monkeypatch.delenv("TRLX_TPU_PROFILE_DIR", raising=False)
    with profiling.maybe_trace():  # no directory: nothing starts
        assert profiling.trace_annotation("x") is None
    assert not seen
    with profiling.maybe_trace(str(tmp_path)):
        assert profiling.trace_annotation("x") is not None
    assert profiling.trace_annotation("x") is None  # off again
    assert seen == {"dir": str(tmp_path), "python_tracer_level": 0}


def test_spans_record_parent_and_a_track_per_thread(session):
    """``args.parent`` is the enclosing open span ON THAT THREAD; each
    thread gets its own ``tid``; request tracks stay clear of both."""
    inside = threading.Event()
    leave = threading.Event()

    def worker():
        with telemetry.span("w_outer"):
            inside.set()
            leave.wait(timeout=10.0)
            with telemetry.span("w_inner"):
                pass

    t = threading.Thread(target=worker)
    with telemetry.span("m_outer"):
        t.start()
        assert inside.wait(timeout=10.0)
        # the worker's span is open now, on another thread: not a parent
        with telemetry.span("m_inner"):
            assert session.tracer.current_span() == "m_inner"
        leave.set()
        t.join(timeout=10.0)
    assert not t.is_alive()
    assert session.tracer.current_span() is None
    ev = {e["name"]: e for e in session.tracer.events}
    assert ev["m_inner"]["args"]["parent"] == "m_outer"
    assert ev["w_inner"]["args"]["parent"] == "w_outer"
    assert "parent" not in ev["m_outer"]["args"]
    assert "parent" not in ev["w_outer"]["args"]
    assert ev["m_outer"]["tid"] == ev["m_inner"]["tid"]
    assert ev["w_outer"]["tid"] == ev["w_inner"]["tid"]
    assert ev["m_outer"]["tid"] != ev["w_outer"]["tid"]
    assert max(e["tid"] for e in ev.values()) < REQUEST_TID_BASE
    assert RequestTrace().tid >= REQUEST_TID_BASE


def test_live_and_handed_over_spans_share_one_zero(session):
    """``span`` and ``add_span`` stamp against the same clock and zero."""
    tracer = session.tracer
    from trlx_tpu.supervisor import monotonic

    start = monotonic()
    with telemetry.span("live"):
        pass
    end = monotonic()
    tracer.add_span("handed", start, end, tid=REQUEST_TID_BASE)
    live, handed = tracer.events[-2], tracer.events[-1]
    assert handed["ts"] <= live["ts"]
    assert live["ts"] + live["dur"] <= handed["ts"] + handed["dur"] + 0.01
    assert not hasattr(tracer, "t0_monotonic")


# --------------------------------------------------------------------- #
# (e) compiles, counted where they happen
# --------------------------------------------------------------------- #


def test_backend_compiles_are_laid_to_the_open_span(session):
    counters = session.registry.counters
    assert counters["compile/backend_compiles"] == 0.0  # predeclared
    x = jnp.arange(7.0)  # made outside: its own programs are not x's
    before = counters["compile/backend_compiles"]
    fresh = jax.jit(lambda a: a * 3.0 + 1.0)
    with telemetry.span("x"):
        fresh(x).block_until_ready()
    assert counters["compile/backend_compiles"] == before + 1
    assert counters["compile/backend_compiles{span=x}"] == 1.0
    assert session.registry.hists["compile/backend_compile_s"].count >= 1
    with telemetry.span("x"):
        fresh(x).block_until_ready()  # cached: neither moves
    assert counters["compile/backend_compiles"] == before + 1
    assert counters["compile/backend_compiles{span=x}"] == 1.0
    assert counters["compile/recompiles"] == 0.0


# --------------------------------------------------------------------- #
# (f) per-token stamps
# --------------------------------------------------------------------- #


def test_token_times_gaps_are_the_itl_observations(session):
    tr = RequestTrace(received=100.0)
    tr.enqueued = 100.0
    stamps = [100.25, 100.30, 100.42, 100.43, 100.75]
    for t in stamps:
        tr.note_token(t)
    assert tr.token_times == stamps
    hist = session.registry.hists["serve/itl"]
    observed = [hist.first] + list(hist.window)
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    assert observed == pytest.approx(gaps) and len(observed) == len(gaps)
    tr.harvested = stamps[-1]
    payload = tr.to_dict()
    assert payload["token_ms"] == pytest.approx(
        [250.0, 300.0, 420.0, 430.0, 750.0]
    )
    assert payload["token_ms"][0] == payload["ttft_ms"]
    assert payload["tokens"] == len(payload["token_ms"])
    assert "token_ms" not in RequestTrace().to_dict()  # no token, no list


# --------------------------------------------------------------------- #
# (b) + (c) the slot scheduler's loop, phase by phase; named programs
# --------------------------------------------------------------------- #

SERVE_PAGED = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8]], max_queue=16, request_timeout=30.0,
    slots=4, page_size=4,
)


@pytest.fixture(scope="module")
def served():
    """A tiny paged slot engine that has answered a few requests, and
    what it left behind: span events, flight records, counters."""
    tel = telemetry.start()
    engine = InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                             serve=SERVE_PAGED)
    sched = SlotScheduler(engine)
    sched.warmup()
    warm_events = len(tel.tracer.events)
    sched.start()
    rows = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [1, 3, 5, 7], [9]]
    try:
        requests = [sched.submit(r, max_new_tokens=5) for r in rows]
        for r in requests:
            assert r.done.wait(timeout=60.0) and r.error is None
    finally:
        sched.stop()
    out = {
        "events": [e for e in tel.tracer.events[warm_events:]
                   if e["ph"] == "X"],
        "flight": sched.flight.snapshot(),
        "counters": dict(tel.registry.counters),
        "itl_count": tel.registry.hists["serve/itl"].count,
        "periods": list(tel.registry.hists["time/serve/slot_step"].window),
        "requests": requests,
        "runtime": sched.runtime,
    }
    telemetry.start()
    return out


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] - 0.01
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 0.01)


def test_scheduler_loop_is_spanned_phase_by_phase(served):
    ev = [e for e in served["events"] if e["tid"] < REQUEST_TID_BASE]
    by = {}
    for e in ev:
        by.setdefault(e["name"], []).append(e)
    assert {"serve/admit", "serve/step_dispatch", "serve/step_fetch",
            "serve/harvest"} <= set(by)
    assert len({e["tid"] for e in ev}) == 1  # all on the worker's track
    steps = by["serve/step_dispatch"]
    fetches = by["serve/step_fetch"]
    assert len(fetches) == len(steps) == len(by["serve/harvest"])
    # a step's period is observed, not spanned (warm-up's first step is)
    assert "serve/slot_step" not in by
    assert len(served["periods"]) == len(steps)
    for fetch, step, harvest, period in zip(
            fetches, steps, by["serve/harvest"], served["periods"]):
        # a step's dispatch, then its fetch, then its harvest: three
        # spans of their own on the worker's track; its period holds
        # the wait for its result
        assert "parent" not in fetch.get("args", {})
        assert fetch["ts"] >= step["ts"] + step["dur"] - 0.01
        assert "parent" not in harvest.get("args", {})
        assert harvest["ts"] >= fetch["ts"] + fetch["dur"] - 0.01
        assert period * 1e6 >= fetch["dur"] - 10.0
    # a step in flight: the next step was dispatched before its fetch
    assert any(nxt["ts"] + nxt["dur"] <= fetch["ts"] + 0.01
               for fetch, nxt in zip(fetches, steps[1:]))
    prefills = [e for e in ev if e["name"].startswith("serve/prefill")]
    assert prefills
    for p in prefills:
        assert p["args"]["parent"] == "serve/admit"
        assert any(_inside(p, a) for a in by["serve/admit"])
    for a in by["serve/admit"]:
        assert "parent" not in a.get("args", {})
    # at most six span records per scheduler iteration
    assert len(ev) <= 6 * len(steps)


def test_flight_records_carry_the_iteration_phases(served):
    flight = served["flight"]
    assert flight
    for rec in flight:
        assert {"admit_ms", "fetch_ms", "harvest_ms", "step_ms",
                "ahead"} <= set(rec)
        assert rec["fetch_ms"] >= 0 and rec["harvest_ms"] >= 0
        # the wait for the result and the harvest both lie inside the step
        assert rec["fetch_ms"] + rec["harvest_ms"] <= rec["step_ms"] + 0.01
    assert any(rec["admit_ms"] > 0 for rec in flight if rec["admitted"])
    # the steps dispatched while the one before was in flight, counted
    assert {rec["ahead"] for rec in flight} == {0, 1}
    assert served["counters"]["serve/steps_ahead"] == sum(
        rec["ahead"] for rec in flight)
    assert [rec["step"] for rec in flight] == list(
        range(1, len(flight) + 1))


def test_serve_programs_carry_their_names(served):
    def lowered_names(fn):
        return [c.runtime_executable().hlo_modules()[0].name
                for c in fn._cache.values()]

    rt = served["runtime"]
    assert lowered_names(rt._step_fn) == ["jit_run_decode_step"]
    prefill = [n for fn in rt._prefill_fns.values() for n in lowered_names(fn)]
    assert prefill and all(n.startswith("jit_run_prefill_b") or
                           n.startswith("jit_run_prefill_sfx_b")
                           for n in prefill)
    assert "jit_run_prefill_b4p8" in prefill
    assert served["counters"]["compile/recompiles"] == 0.0


def test_served_requests_keep_a_stamp_per_token(served):
    gaps = 0
    for r in served["requests"]:
        assert len(r.trace.token_times) == len(r.result) == 5
        assert r.trace.token_times == sorted(r.trace.token_times)
        assert len(r.trace.to_dict()["token_ms"]) == 5
        gaps += len(r.trace.token_times) - 1
    assert gaps == served["itl_count"]
