"""Unified telemetry layer (trlx_tpu.telemetry): registry semantics, span
tracing + Chrome-trace JSONL validity, fault-counter wiring driven by the
fault-injection helpers, the CPU smoke learn() emission, and the
zero-overhead-when-disabled contract.

Also covers the tracker fixes that ride this PR: JsonlTracker's lazy
parent-dir creation + fsync-on-finish, ResilientTracker finishing the
original failed sink after degradation, and WandbTracker's step reuse for
emissions without an ``iter`` key.
"""

import json
import os
import types

import pytest

from trlx_tpu import telemetry
from trlx_tpu.telemetry.registry import MetricsRegistry, TimingHist
from trlx_tpu.telemetry.tracer import SpanTracer


@pytest.fixture(autouse=True)
def _clean_session():
    """Each test starts and ends without an active session (constructing a
    trainer inside a test starts one; don't leak it across tests)."""
    telemetry.stop()
    yield
    telemetry.stop()


# --------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------- #


def test_registry_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    reg.inc("fault/skipped_steps")
    reg.inc("fault/skipped_steps", 2)
    reg.set_gauge("device/hbm_in_use_gb", 3.5)
    reg.set_gauge("device/hbm_in_use_gb", 4.0)  # last value wins
    for s in (0.5, 0.01, 0.02, 0.03, 0.04):  # first is compile-laden
        reg.observe("time/step", s)

    flat = reg.tracker_stats()
    assert flat["fault/skipped_steps"] == 3.0
    assert flat["device/hbm_in_use_gb"] == 4.0
    assert flat["time/step"] == 0.04  # histograms emit the LAST duration
    assert all(isinstance(v, float) for v in flat.values())

    stats = reg.hists["time/step"].stats()
    assert stats["count"] == 5
    assert stats["first_s"] == 0.5  # kept apart from the window
    assert stats["max_s"] == 0.5
    assert stats["total_s"] == pytest.approx(0.6)
    # steady-state quantiles exclude the first (compile) observation
    assert 0.01 <= stats["p50_s"] <= 0.03
    assert stats["p95_s"] <= 0.04
    # cache-miss signal: first call dwarfs the steady state
    assert stats["first_over_p50"] > 10


def test_timing_hist_single_observation():
    h = TimingHist()
    h.observe(0.2)
    s = h.stats()
    assert s["p50_s"] == 0.2 and s["max_s"] == 0.2 and s["count"] == 1


# --------------------------------------------------------------------- #
# span tracer: nesting + Chrome-trace JSONL validity
# --------------------------------------------------------------------- #


def test_span_nesting_and_chrome_trace_jsonl(tmp_path):
    reg = MetricsRegistry()
    tracer = SpanTracer(registry=reg)
    with tracer.span("rollout"):
        with tracer.span("reward_fn"):
            pass
        with tracer.span("reward_fn"):
            pass

    path = tracer.write_jsonl(str(tmp_path / "trace.jsonl"))
    lines = open(path).read().splitlines()
    assert len(lines) == 3
    events = [json.loads(line) for line in lines]  # every line parses
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert ev["name"] in ("rollout", "reward_fn")
    # inner spans close before the outer one and nest inside its interval
    outer = next(e for e in events if e["name"] == "rollout")
    inners = [e for e in events if e["name"] == "reward_fn"]
    for inner in inners:
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    # first occurrence of each name is flagged (compile attribution)
    assert outer.get("args", {}).get("first_call") is True
    assert inners[0].get("args", {}).get("first_call") is True
    assert inners[1]["args"] == {"parent": "rollout"}
    # spans fed the registry: time/* histograms + compile/* first gauges
    assert reg.hists["time/rollout"].count == 1
    assert reg.hists["time/reward_fn"].count == 2
    assert "compile/rollout_first_s" in reg.gauges


def test_tracer_bounds_events_and_marks_drop(tmp_path):
    tracer = SpanTracer(registry=MetricsRegistry(), max_events=2)
    for _ in range(4):
        with tracer.span("s"):
            pass
    assert len(tracer.events) == 2 and tracer.dropped == 2
    lines = open(tracer.write_jsonl(str(tmp_path / "t.jsonl"))).read().splitlines()
    assert "dropped" in json.loads(lines[-1])["name"]


# --------------------------------------------------------------------- #
# zero-overhead-by-default: disabled telemetry records NOTHING
# --------------------------------------------------------------------- #


def test_disabled_records_no_spans_or_metrics():
    assert telemetry.current() is None
    with telemetry.span("rollout"):  # must be a pure no-op
        telemetry.inc("fault/skipped_steps")
        telemetry.set_gauge("g", 1.0)
        telemetry.observe("time/x", 0.5)
    assert telemetry.current() is None
    assert telemetry.summary() == {}

    # a session stopped mid-run stops accumulating: no span records land
    session = telemetry.start()
    with telemetry.span("a"):
        pass
    n_events = len(session.tracer.events)
    telemetry.stop()
    with telemetry.span("b"):
        telemetry.inc("late_counter")
    assert len(session.tracer.events) == n_events
    assert "late_counter" not in session.registry.counters
    assert "time/b" not in session.registry.hists


def test_config_gate_train_telemetry_false():
    config = types.SimpleNamespace(train=types.SimpleNamespace(
        telemetry=False, checkpoint_dir="ckpts"))
    assert telemetry.start_from_config(config) is None
    assert telemetry.current() is None


def test_config_gate_resolves_run_dir():
    config = types.SimpleNamespace(train=types.SimpleNamespace(
        telemetry=True, telemetry_dir="", checkpoint_dir="ckpts/x"))
    session = telemetry.start_from_config(config)
    assert session.run_dir == "ckpts/x" and not session.force_dir
    # no checkpoint dir on disk -> nothing written (no stray files)
    assert session.write() is None
    config.train.telemetry_dir = "runs/y"
    session = telemetry.start_from_config(config)
    assert session.run_dir == "runs/y" and session.force_dir


# --------------------------------------------------------------------- #
# fault counters, driven by the fault-injection helpers (test_faults)
# --------------------------------------------------------------------- #


def test_step_guard_drives_fault_counters():
    from trlx_tpu.utils.faults import DivergenceError, StepGuard

    session = telemetry.start()
    guard = StepGuard(max_bad_steps=2, rollback_fn=lambda: "ck",
                      log=lambda s: None)
    guard.observe(bad=True, step=1)
    guard.observe(bad=True, step=2)  # streak -> rollback
    counters = session.registry.counters
    assert counters["fault/skipped_steps"] == 2.0
    assert counters["fault/rollbacks"] == 1.0
    guard.observe(bad=True, step=3)
    with pytest.raises(DivergenceError):
        guard.observe(bad=True, step=4)  # second strike
    assert counters["fault/skipped_steps"] == 4.0
    assert counters["fault/divergence_aborts"] == 1.0


def test_retry_call_drives_host_retry_counters():
    from trlx_tpu.utils.faults import retry_call

    session = telemetry.start()
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("transient")
        return x

    assert retry_call(flaky, 7, retries=2, backoff=0.0,
                      log=lambda m: None) == 7
    assert session.registry.counters["fault/host_retries"] == 2.0

    with pytest.raises(RuntimeError):
        retry_call(lambda: (_ for _ in ()).throw(RuntimeError("perm")),
                   retries=1, backoff=0.0, log=lambda m: None)
    assert session.registry.counters["fault/host_giveups"] == 1.0


def test_tracker_degradation_drives_fault_counters(capsys):
    from tests.test_faults import _AlwaysFails
    from trlx_tpu.utils.trackers import ResilientTracker

    session = telemetry.start()
    t = ResilientTracker(_AlwaysFails(), retries=0, backoff=0.0,
                         max_consecutive_failures=2)
    t({"iter": 1})
    t({"iter": 2})  # threshold: degrade
    counters = session.registry.counters
    assert counters["fault/tracker_emissions_lost"] == 2.0
    assert counters["fault/tracker_degraded"] == 1.0
    assert t.degraded


def test_checkpoint_counters_and_save_span(tmp_path):
    from tests.test_faults import _components
    from trlx_tpu.utils.checkpoint import (
        restore_components,
        save_step_checkpoint,
    )

    session = telemetry.start()
    run = str(tmp_path / "run")
    save_step_checkpoint(_components(1.0), run, step=1)
    # crash debris cleared by retention counts as a fault event
    os.makedirs(os.path.join(run, "step_5.tmp-99"))
    save_step_checkpoint(_components(2.0), run, step=2, keep=4)
    restore_components(_components(0.0), run)
    counters = session.registry.counters
    assert counters["checkpoint/saves"] == 2.0
    assert counters["checkpoint/restores"] == 1.0
    assert counters["fault/checkpoint_debris_cleared"] >= 1.0
    assert session.registry.hists["time/checkpoint_save"].count == 2


def test_preemption_signal_counts():
    import signal

    from trlx_tpu.utils.preemption import PreemptionGuard

    session = telemetry.start()
    with PreemptionGuard(enabled=True) as guard:
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.poll()
    assert session.registry.counters["fault/preempt_sigterm"] == 1.0


# --------------------------------------------------------------------- #
# CPU smoke: the full PPO loop emits the observability payload
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    from tests.test_ppo_e2e import PROMPTS, make_config, reward_fn
    from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
    from trlx_tpu.utils.tokenizer import ByteTokenizer

    telemetry.stop()
    tmp = str(tmp_path_factory.mktemp("telemetry_run"))
    config = make_config(total_steps=4, epochs=2, ppo_epochs=1,
                         num_rollouts=32, chunk_size=16, batch_size=16)
    config.train.log_interval = 1
    config.train.telemetry_dir = tmp
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    orch.make_experience(config.method.num_rollouts)
    logs = []
    trainer.learn(log_fn=logs.append)
    return tmp, logs


def test_smoke_learn_emits_time_throughput_fault_keys(smoke_run):
    _, logs = smoke_run
    iter_logs = [s for s in logs if "time/rollout" in s]
    assert iter_logs, "no emission carried the time/* phase breakdown"
    stats = iter_logs[-1]
    assert stats["time/rollout"] > 0
    assert stats["time/ppo_update"] > 0
    assert stats["throughput/tokens_per_sec"] > 0
    assert stats["throughput/samples_per_sec"] > 0
    # fault counters present from the first emission (zeros, not absent)
    assert stats["fault/skipped_steps"] == 0.0
    assert "fault/rollbacks" in stats and "fault/host_retries" in stats
    # first-call (compile-laden) latency of the jitted update is exposed
    assert stats["compile/ppo_update_first_s"] > 0
    # everything on the stream is a plain float (tracker protocol)
    assert all(isinstance(v, (int, float)) for v in stats.values())


def test_smoke_learn_writes_summary_and_valid_trace(smoke_run):
    tmp, _ = smoke_run
    summary = json.load(open(os.path.join(tmp, "telemetry.json")))
    assert summary["metric"] == "ppo_learn_samples_per_sec"
    assert summary["value"] > 0 and summary["unit"] == "samples/s"
    assert summary["counters"]["fault/skipped_steps"] == 0.0
    timings = summary["timings"]
    for phase in ("time/rollout", "time/ppo_update", "time/reward_fn"):
        assert timings[phase]["count"] >= 1
        assert timings[phase]["p50_s"] >= 0
        assert timings[phase]["max_s"] >= timings[phase]["p50_s"]

    # Chrome-trace JSONL: every line parses and carries ph/ts/dur
    lines = open(os.path.join(tmp, "trace.jsonl")).read().splitlines()
    assert len(lines) >= 4
    names = set()
    for line in lines:
        ev = json.loads(line)
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        names.add(ev["name"])
    assert {"rollout", "reward_fn", "ppo_update"} <= names


def test_smoke_learn_names_the_ppo_cycle_phase_by_phase(smoke_run):
    """The rollout span splits into fetch / decode_text / reward_fn /
    store; the dispatch has a span of its own; the wait for the update
    program has a name (``ppo_stats_fetch``) right after ``ppo_update``'s
    dispatch."""
    tmp, _ = smoke_run
    events = [json.loads(line)
              for line in open(os.path.join(tmp, "trace.jsonl"))]
    by = {}
    for ev in events:
        by.setdefault(ev["name"], []).append(ev)
    for child in ("rollout_fetch", "rollout_decode_text", "reward_fn",
                  "rollout_store"):
        assert len(by[child]) == 2 * len(by["rollout"])  # one a chunk
        assert all(e["args"]["parent"] == "rollout" for e in by[child])
    assert len(by["rollout_dispatch"]) == len(by["rollout"])
    assert all(e.get("args", {}).get("parent") in (None, "rollout_refresh")
               for e in by["rollout_dispatch"])
    # log_interval 1: every update is followed by its fetch
    assert len(by["ppo_stats_fetch"]) == len(by["ppo_update"]) >= 2
    for update, fetch in zip(by["ppo_update"], by["ppo_stats_fetch"]):
        assert fetch["ts"] >= update["ts"] + update["dur"] - 0.01
        assert "parent" not in fetch.get("args", {})
    assert "rollout_dispatch_stale" not in by and "rollout_harvest" not in by


def test_trainer_with_telemetry_false_records_nothing():
    """The acceptance contract: a disabled run produces NO span records —
    the reference-parity metrics stream, zero overhead."""
    from tests.test_ppo_e2e import make_config
    from trlx_tpu.utils.loading import get_model

    config = make_config(total_steps=2, epochs=1)
    config.train.telemetry = False
    get_model(config.model.model_type)(config)
    assert telemetry.current() is None
    with telemetry.span("rollout"):
        pass
    assert telemetry.current() is None and telemetry.summary() == {}


# --------------------------------------------------------------------- #
# tracker satellite fixes
# --------------------------------------------------------------------- #


def test_jsonl_tracker_creates_missing_parent_dir_and_fsyncs(tmp_path):
    from trlx_tpu.utils.trackers import JsonlTracker

    path = str(tmp_path / "runs" / "x" / "log.jsonl")  # dir doesn't exist
    t = JsonlTracker(path)
    t({"iter": 1, "loss": 0.5})
    t({"iter": 2, "loss": 0.4})
    t.finish()  # fsyncs; must not raise
    lines = [json.loads(x) for x in open(path)]
    assert [x["iter"] for x in lines] == [1, 2]

    # finish() on a tracker that never emitted: no file, no error
    JsonlTracker(str(tmp_path / "never" / "log.jsonl")).finish()


def test_resilient_finish_also_finishes_failed_inner(capsys):
    from trlx_tpu.utils.trackers import ResilientTracker

    class _WandbLike:
        def __init__(self):
            self.finished = False

        def __call__(self, stats):
            raise ConnectionError("api down")

        def finish(self):
            self.finished = True  # the leaked-process fix: run closed

    inner = _WandbLike()
    t = ResilientTracker(inner, retries=0, backoff=0.0,
                         max_consecutive_failures=2)
    t({"iter": 1})
    t({"iter": 2})  # degrade to stdout
    assert t.degraded and t.inner is not inner
    t.finish()
    assert inner.finished, "degraded sink's original finish() not attempted"

    # and a failed-inner finish that raises is still swallowed-with-notice
    inner.finish = lambda: (_ for _ in ()).throw(ConnectionError("down"))
    t.finish()
    assert "ignored" in capsys.readouterr().out


def test_wandb_tracker_reuses_last_step_when_iter_absent():
    from trlx_tpu.utils.trackers import WandbTracker

    logged = []

    class _StubWandb:
        @staticmethod
        def log(payload, step=None):
            logged.append((payload, step))

        class Table:
            def __init__(self, columns, rows):
                self.columns, self.rows = columns, rows

    t = WandbTracker.__new__(WandbTracker)
    t._wandb = _StubWandb
    t._last_step = None
    t({"iter": 5, "loss": 1.0})
    t({"mean_score": 0.5,
       "samples_table": {"columns": ["s"], "rows": [["x"]]}})  # no iter
    t({"iter": 7, "loss": 0.9})
    t({"eval_only": 1.0})
    assert [s for _, s in logged] == [5, 5, 7, 7]
    assert logged[1][0]["mean_score"] == 0.5


# --------------------------------------------------------------------- #
# the device the numbers are divided by
# --------------------------------------------------------------------- #


def _fake_device(platform, kind, stats=None):
    return types.SimpleNamespace(
        platform=platform, device_kind=kind, memory_stats=lambda: stats
    )


def test_peak_flops_answers_from_device_kind(monkeypatch):
    """v5e (device_kind "TPU v5 lite", read off the chip) is 197e12; a
    TPU kind the table does not know is an error, never a default; the
    CPU backend has no peak and MFU is simply omitted there."""
    import jax

    from trlx_tpu.telemetry import flops

    assert flops.peak_flops() is None  # this CPU run
    assert flops.mfu_estimate(1e3, 1e9) is None
    monkeypatch.setattr(
        jax, "devices", lambda: [_fake_device("tpu", "TPU v5 lite")]
    )
    assert flops.peak_flops() == 197e12
    assert flops.mfu_estimate(1e3, 197e9) == pytest.approx(1.0)
    monkeypatch.setattr(
        jax, "devices", lambda: [_fake_device("tpu", "TPU v9 mega")]
    )
    with pytest.raises(ValueError, match="TPU v9 mega"):
        flops.peak_flops()


def test_device_monitor_is_silent_only_where_the_backend_has_no_stats(
    monkeypatch,
):
    import jax

    from trlx_tpu.telemetry import device

    monkeypatch.setattr(device, "_available", True)
    reg = MetricsRegistry()
    device.sample_device_stats(reg)  # CPU: no stats, latches silent
    assert device._available is False and not reg.gauges

    monkeypatch.setattr(device, "_available", True)
    monkeypatch.setattr(
        jax, "local_devices", lambda: [_fake_device("tpu", "TPU v5 lite")]
    )
    with pytest.raises(RuntimeError, match="memory_stats"):
        device.sample_device_stats(reg)
    monkeypatch.setattr(
        jax, "local_devices", lambda: [_fake_device(
            "tpu", "TPU v5 lite",
            {"bytes_in_use": 2**30, "bytes_limit": 16 * 2**30},
        )],
    )
    device.sample_device_stats(reg)
    assert reg.gauges["device/hbm_in_use_gb"] == 1.0
    assert reg.gauges["device/hbm_utilization"] == 1 / 16
