"""serve_closed_cohere2: ``serve_closed``'s closed loop for ``arch:
cohere2_moe`` and the ``longshort`` mixes. What differs from the parent class:

- the weights come from ``lib/weights_cohere2_moe.py`` and the reference from
  ``lib/reference_cohere2_moe.py`` (one sequence at a time, in blocks);
- the traffic is ``lib/traffic_longshort.py``'s: in set-up every long client's
  document goes once through the engine's own path (a request of the document
  and one new token: prefilled in chunks, committed to the prefix cache), so
  that every turn of the window is a prefix hit + a fresh question;
- the sample that decides ``correct`` holds the cell's ``check.long`` longest
  finished long sessions and short requests besides, and must hold a token
  that was decoded after a window-class page of its slot was released;
- the window also keeps what the new per-layer readers read: the decode
  program's op metadata (instruction -> named scope, for ``scope_ms``) and
  each request's prefix hit;
- what set-up left on the heap (the compiled programs, the trie of the
  documents, the schedule) is frozen before the clients start, as a server
  does after start-up: a full pass of Python's collector over it holds every
  thread for about 90 ms on the chip's host, and frozen, none can fall in a
  window. ``notes.timeline.gc`` says what the collector did inside the
  window (the iterations of 130-160 ms with nothing admitted that remain are
  not the collector's: PERF.md section 6, PR 29).

``fault`` in the cell file (the planted faults of ``benchmarks/tests``) is
honoured at rehearsal size only."""

import gc
import importlib
import random
import re
import threading
import time

import jax
import numpy as np

from benchmarks.drivers import common, serve_closed
from benchmarks.lib import reference_cohere2_moe as R
from benchmarks.lib import weights_cohere2_moe as WC


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} of a compiled program's text."""
    found = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", hlo_text, flags=re.M)
    return dict(found)


class StampedTracer:
    """The harness's tracer, with the engine's clock read as the trace starts and stops: the per-layer
    readers count the steps of the very interval that was traced."""

    def __init__(self, tracer, clock):
        self.tracer, self.clock, self.interval = tracer, clock, None

    def start(self):
        self.tracer.start()
        self.began = self.clock()

    def stop(self):
        self.interval = (self.began, self.clock())
        self.tracer.stop()


class Cell(serve_closed.Cell):
    def __init__(self, env):
        super().__init__(env)
        self.fault = self.cell.get("fault")
        if self.fault and not env.get("rehearse"):
            raise ValueError("a planted fault runs at rehearsal size only")

    def setup(self):
        from trlx_tpu import telemetry
        from trlx_tpu.serve import InferenceEngine, ServeConfig
        from trlx_tpu.serve.slots import SlotScheduler
        from trlx_tpu.supervisor import monotonic

        self.clock = monotonic  # the one clock the serve engine stamps its traces with
        telemetry.start()
        spec = dict(self.spec)
        if self.fault == "expert_left_out":  # the program holds one expert fewer than the reference is told
            spec["experts_held"] -= 1
        config = common.trl_config(
            spec, self.cell["model"], self.cell.get("train", {}),
            {"gen_kwargs": {"do_sample": not self.mix.get("greedy", True)}}, self.seed)
        serve = ServeConfig.from_dict(self.cell["serve"])
        from trlx_tpu.models.transformer import ArchFlags

        ArchFlags.for_spec(config.model.resolve_spec())  # a program that lacks the arch raises here: no weight is made
        def make():  # the engine owns what it makes: the stacked trunk is released as it is split by layer
            params = jax.block_until_ready(WC.hydra_weights(spec, self.seed, self.k, self.store_dtype))
            self.say("weights made from the seed")
            return params

        self.engine = InferenceEngine(config, serve=serve, params=make)
        self.say("engine built")
        self.sched = SlotScheduler(self.engine)
        if self.fault == "window_page_early":  # every window-class page is released a page before its time
            release, page = self.sched._release_behind, self.engine.page_size_tokens()
            self.sched._release_behind = lambda live, pos, slot=None: release(live, pos + page, slot)
        self.warmup_s = self.sched.warmup()
        self.say(f"warmed: { {k: round(v, 1) for k, v in self.warmup_s.items()} }")
        rt = self.sched.runtime
        extra = (np.zeros((rt.num_slots, rt.ring_pages), np.int32),) if rt.two_class else ()
        e = self.engine
        self.decode_scopes = op_scopes(rt._decode_fn().compiled_for(
            e.blocks, e.embed, e.ln_f, rt.pool, rt.state, np.int32(0), *extra).as_text())
        self.sched.start()
        self.registry = telemetry.current().registry
        generator = importlib.import_module(f"benchmarks.lib.{self.mix['generator']}")
        documents, clients = generator.serve_requests(self.mix, self.seed)
        self.n_long = len(documents)
        # every document once through the engine's own path: chunked prefill, committed to the prefix cache
        pending = [self.sched.submit(doc, max_new_tokens=1) for doc in documents]
        for req in pending:
            if not req.done.wait(timeout=serve_closed.WAIT_S * 4) or req.error is not None:
                raise RuntimeError(f"a document was not committed in set-up: {req.error!r}")
        chunks = self.registry.counters.get("serve/prefill_chunks", 0)
        self.say(f"{len(documents)} documents committed ({sum(map(len, documents))} tokens, {chunks} chunks)")
        self.threads = [
            threading.Thread(target=self._client, args=(i, reqs), name=f"bench-client-{i}", daemon=True)
            for i, reqs in enumerate(clients)
        ]
        gc.collect()
        gc.freeze()  # set-up's heap leaves the collector's sight; ``release`` gives it back
        for t in self.threads:
            t.start()
        time.sleep(self.mix.get("ramp_s", 3.0))

    def release(self):
        gc.unfreeze()  # so that the engine and its device buffers go before the reference runs
        super().release()

    def window(self, seconds, tracer=None):
        prompt0 = (self.sched._prompt_tokens_total, self.sched._prefix_tokens_saved)
        counters0 = dict(self.registry.counters)
        tracer = StampedTracer(tracer, self.clock) if tracer else None
        passes, began = [], {}

        def collector(phase, info):  # every pass of the collector inside the window: generation, how long
            if phase == "start":
                began[info["generation"]] = self.clock()
            elif info["generation"] in began:
                start = began.pop(info["generation"])
                passes.append((info["generation"], start, (self.clock() - start) * 1e3))

        gc.callbacks.append(collector)
        try:
            m = super().window(seconds, tracer)
        finally:
            gc.callbacks.remove(collector)
        passes = [(generation, ms) for generation, start, ms in passes if m["t0"] <= start <= m["t1"]]
        full = [ms for generation, ms in passes if generation == 2]
        self.timeline["gc"] = {"passes": len(passes), "full_passes": len(full), "full_pass_max_ms": max(full, default=0.0),
                               "pass_max_ms": max((ms for _, ms in passes), default=0.0)}
        if tracer:
            m["traced"] = tracer.interval
        # what the page pool did since the window opened (to when the last client was answered): a document
        # prefilled again in chunks, or pages evicted, is a prefix hit that was lost
        self.timeline["pool"] = {
            name: self.registry.counters.get(name, 0) - counters0.get(name, 0)
            for name in ("serve/prefill_chunks", "serve/window_pages_freed", "serve/evicted_pages",
                         "serve/evicted_pages{class=window}", "serve/admissions")}
        for r in self.records:
            tr = r["req"].trace if r["req"] is not None else None
            r["prefix_blocks_hit"] = tr.prefix_blocks_hit if tr is not None else 0
        rt = self.sched.runtime
        m.update(decode_scopes=self.decode_scopes, page_size=rt.page_size, ring_pages=rt.ring_pages,
                 n_long=self.n_long,
                 # since the window opened, to when the last client was answered
                 prompt_tokens=self.sched._prompt_tokens_total - prompt0[0],
                 prefix_tokens_saved=self.sched._prefix_tokens_saved - prompt0[1])
        return m

    def _sample(self, done):
        """The ``check.long`` longest finished long sessions, and short requests drawn from the seed."""
        done = sorted(done, key=lambda r: (r["submit"], r["client"]))
        eos = 256
        self.lengths_wrong = sum(
            1 for r in done if not (r["n_out"] == r["max_new"] or (r["n_out"] < r["max_new"] and r["last"] == eos)))
        want, n_long = self.cell["check"]["sample"], self.cell["check"]["long"]
        long = sorted((r for r in done if r["client"] < self.n_long), key=lambda r: -(r["prompt_len"] + r["n_out"]))
        short = [r for r in done if r["client"] >= self.n_long]
        random.Random(self.seed).shuffle(short)
        picked = long[:n_long] + short[:want - min(n_long, len(long))]
        # a token decoded after a window-class page of its slot was released: any beyond window + 2 pages
        rt = self.sched.runtime
        self.behind_window = sum(max(r["prompt_len"] + r["n_out"] - rt.ring_pages * rt.page_size, 0) for r in picked)
        self.long_checked = sum(1 for r in picked if r["prompt_len"] + r["n_out"] > self.cell["check"]["long_context"])
        return [(list(r["req"].tokens), list(r["req"].result)) for r in picked]

    def check(self, probe=False):
        limits, m = self.cell["correct"], self.measured
        readings = {n: m[n] for n in ("compiles_in_window", "fault_counters_moved", "admitted_not_answered",
                                      "clients_alive")}
        readings["answers_wrong_length"] = self.lengths_wrong
        readings["answers_checked_min"] = -len(self.sample)  # the sample has to be there to check
        readings["long_sessions_checked_min"] = -self.long_checked
        readings["tokens_behind_window_min"] = -min(self.behind_window, 1)
        extra = {"timeline": self.timeline}
        if self.sample:
            sample = self._faulted(self.sample)
            gaps, cgaps, note = R.served_gaps(
                self.spec, self.seed, sample, self.store_dtype, control="fp8" if probe else None,
                pad_to=self.cell["check"].get("pad_to", 1024), router_note=probe)
            readings["served_gap_max"], readings["served_gap_mean"] = float(gaps.max()), float(gaps.mean())
            # the share of served tokens that are not the reference's best: what a lower precision moves most (many
            # small gaps), where a router near-tie resolved otherwise makes few and large ones (PERF.md section 2)
            readings["served_off_best_share"] = float((gaps > 0).mean())
            extra.update(served_tokens_checked=int(gaps.size), served_tokens_off_best=int((gaps > 0).sum()), **note)
            if cgaps is not None:
                extra["probe"] = {"control_fp8": {"served_gap_max": float(cgaps.max()),
                                                  "served_gap_mean": float(cgaps.mean()),
                                                  "served_off_best_share": float((cgaps > 0).mean()),
                                                  "tokens_off_best": int((cgaps > 0).sum())}}
        compared = {n: {"value": v, "limit": limits[n], "ok": bool(v <= limits[n])}
                    for n, v in readings.items() if n in limits}
        ok = all(c["ok"] for c in compared.values()) and "served_gap_max" in compared
        return ok, compared, extra

    def _faulted(self, sample):
        """The planted fault ``served_token``: one served token of the first sampled answer altered after the window."""
        if self.fault != "served_token":
            return sample
        prompt, served = sample[0]
        served = list(served)
        served[len(served) // 2] = (served[len(served) // 2] + 1) % self.spec["vocab_size"]
        return [(prompt, served)] + sample[1:]
