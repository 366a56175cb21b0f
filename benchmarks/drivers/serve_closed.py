"""serve_closed: a closed loop of clients against ``SlotScheduler.submit`` on
an ``InferenceEngine``. Each client sends its next request when the last one
has been answered (callers that wait for a reply: an evaluation harness,
rollout workers). The clients start in set-up and ramp for ``ramp_s``; the
window opens on a loop that is already in its steady state.

Decoding is greedy, so that every served token can be checked: ``check``
runs the plain reference over a sample of finished requests and reads how
far each served token's reference logit lies below the reference's best."""

import gc
import random
import threading
import time

import jax

from benchmarks.drivers import common
from benchmarks.lib import gaps as G
from benchmarks.lib import reference as R
from benchmarks.lib import traffic as T

WAIT_S = 90.0  # an answer may come this late after the window closed; then it is missing


class Cell:
    def __init__(self, env):
        self.cell, self.spec, self.mix, self.seed = env["cell"], env["spec"], env["mix"], env["seed"]
        self.k = self.cell["model"]["num_layers_unfrozen"]
        self.store_dtype = common.DTYPES[self.cell["model"].get("param_dtype", "bfloat16")]
        self.say = env.get("say", lambda text: None)
        self.compiles = common.CompileCounter()
        self.records = []
        self.stop = threading.Event()

    # ------------------------------------------------------------ set-up
    def setup(self):
        from trlx_tpu import telemetry
        from trlx_tpu.serve import InferenceEngine, ServeConfig
        from trlx_tpu.serve.slots import SlotScheduler
        from trlx_tpu.supervisor import monotonic

        self.clock = monotonic  # the one clock the serve engine stamps its traces with
        telemetry.start()
        config = common.trl_config(
            self.spec, self.cell["model"], self.cell.get("train", {}),
            {"gen_kwargs": {"do_sample": not self.mix.get("greedy", True)}}, self.seed)
        serve = ServeConfig.from_dict(self.cell["serve"])
        params = common.hydra_weights(self.spec, self.seed, self.k, self.store_dtype, False,
                                      trainable_store_dtype=self.store_dtype)
        jax.block_until_ready(params)
        self.say("weights made from the seed")
        self.engine = InferenceEngine(config, serve=serve, params=params)
        del params
        self.say("engine built")
        self.sched = SlotScheduler(self.engine)
        self.warmup_s = self.sched.warmup()
        self.say(f"warmed: { {k: round(v, 1) for k, v in self.warmup_s.items()} }")
        self.sched.start()
        self.registry = telemetry.current().registry
        self.threads = [
            threading.Thread(target=self._client, args=(i, reqs), name=f"bench-client-{i}", daemon=True)
            for i, reqs in enumerate(T.serve_requests(self.mix, self.seed))
        ]
        for t in self.threads:
            t.start()
        time.sleep(self.mix.get("ramp_s", 3.0))

    def _client(self, i, reqs):
        j = 0
        while not self.stop.is_set():
            toks, max_new = reqs[j % len(reqs)]
            j += 1
            rec = {"client": i, "submit": self.clock(), "prompt_len": len(toks), "max_new": max_new,
                   "req": None, "error": None}
            try:
                with common.annotation("bench/submit"):
                    rec["req"] = self.sched.submit(toks, max_new_tokens=max_new)
            except Exception as e:  # refused: counts as failed, and the loop goes on
                rec["error"] = repr(e)
                self.records.append(rec)
                time.sleep(0.05)
                continue
            self.records.append(rec)
            rec["req"].done.wait(timeout=WAIT_S + 60.0)

    def _fresh_hists(self):
        """The stock span histograms keep their last 512 observations; the
        window wants all of them. Put large ones in their place, by name.
        (Token gaps need none: every request keeps its own token stamps.)"""
        from trlx_tpu.telemetry import TimingHist

        hists = {}
        for name in [k for k in list(self.registry.hists) if k.startswith("time/serve/")]:
            h = TimingHist(window=1_000_000)
            h.first = 0.0  # so that every observation lands in the window
            self.registry.hists[name] = hists[name] = h
        return hists

    # ------------------------------------------------------------ window
    def _itl_observed(self):
        """How many token gaps the program has put into its own ``serve/itl`` so far."""
        hist = self.registry.hists.get("serve/itl")
        return hist.count if hist is not None else 0

    def _token_gaps(self, flight):
        """Every token gap of every request sent, from the requests' own
        stamps, each with the stamp it ended at and the label of the
        scheduler's step it ended in (what ran ahead of that step); and
        how many steps the labelling could not join to their admissions."""
        sent = [r for r in self.records if r["token_times"]]
        labels, mislabelled = G.step_labels(
            flight, [(r["admitted"], r["bucket"]) for r in sent if r["admitted"] and r["bucket"]])
        return G.label_gaps([g for r in sent for g in G.request_gaps(r["token_times"])], flight, labels), mislabelled

    def window(self, seconds, tracer=None):
        before = (self.compiles.count, common.fault_counters())
        hists = self._fresh_hists()
        itl_before = self._itl_observed()
        t0 = self.clock()
        if tracer:
            tracer.start()
            time.sleep(min(self.cell.get("trace", {}).get("seconds", 3.0), seconds))
            tracer.stop()
        time.sleep(max(t0 + seconds - self.clock(), 0.0))
        t1 = self.clock()
        itl_observed = self._itl_observed() - itl_before
        observed = {name: list(h.window) for name, h in hists.items()}
        flight_all = self.sched.flight.snapshot() if self.sched.flight else []
        flight = [r for r in flight_all if t0 <= r["t"] <= t1]
        after = (self.compiles.count, common.fault_counters())
        self.stop.set()
        for t in self.threads:
            t.join(timeout=WAIT_S + 90.0)
        for r in self.records:  # plain numbers: the request objects go away with the engine
            req = r["req"]
            ok = req is not None and req.done.is_set() and req.error is None and bool(req.result)
            tr = req.trace if req is not None else None
            r.update(ok=ok, n_out=len(req.result) if ok else 0, last=req.result[-1] if ok else None,
                     token_times=list(tr.token_times) if tr is not None else [],
                     bucket=tr.bucket if tr is not None else None,
                     **{f: getattr(tr, f) if tr is not None else 0.0
                        for f in ("enqueued", "admitted", "first_token", "harvested")})
        sent = [r for r in self.records if t0 <= r["submit"] < t1]
        done = [r for r in sent if r["ok"]]
        first_in = sum(1 for r in self.records if r["ok"] and t0 <= r["first_token"] <= t1)
        self.sample = self._sample(done)
        # a gap counts where its end stamp lies in the window, as a first token does
        gaps, mislabelled = self._token_gaps(flight_all)
        gaps = [g for g in gaps if t0 <= g[1] <= t1]
        # where a run reads far off, these say whether the engine stalled, when, and in which phase
        between = [(b["t"] - a["t"], a["t"] - t0) for a, b in zip(flight, flight[1:])]
        longest = max(between) if between else (0.0, 0.0)
        slowest = max(flight, key=lambda r: r["step_ms"], default=None)
        self.timeline = {"steps": len(flight), "longest_step_gap_ms": longest[0] * 1e3, "at_s": longest[1],
                         "slot_step_max_ms": max(observed.get("time/serve/slot_step", [0.0])) * 1e3,
                         "ttft_max_ms": max(((r["first_token"] - r["submit"]) * 1e3 for r in done), default=0.0),
                         "longest_step": slowest and {
                             "at_s": slowest["t"] - t0,
                             **{k: slowest[k] for k in ("step", "active", "admitted", "step_ms", "admit_ms", "fetch_ms",
                                                        "harvest_ms") if k in slowest}},
                         # the program's own count of gaps beside the benchmark's, from the requests' stamps
                         "token_gaps": {"by_token_times": len(gaps), "by_serve_itl_count": itl_observed,
                                        "steps_mislabelled": mislabelled}}
        counters = dict(self.registry.counters)
        self.measured = {
            "t0": t0, "t1": t1, "seconds": t1 - t0, "sent": sent, "done": done,
            # every output token stamped inside the window: a first token, or one gap after the token before it
            "tokens_emitted": first_in + len(gaps),
            "requests": [r for r in self.records if r["ok"]],
            "gaps": gaps,
            "slot_step_s": observed.get("time/serve/slot_step", []),
            "prefill_s": [x for k, v in observed.items() if k.startswith("time/serve/prefill") for x in v],
            "flight": flight, "slots": self.engine.slot_count(),
            "compiles_in_window": after[0] - before[0],
            "fault_counters_moved": sum(abs(after[1].get(k, 0) - before[1].get(k, 0)) for k in after[1]),
            "admitted_not_answered": counters.get("serve/admissions", 0) - counters.get("serve/responses", 0)
            - sum(1 for r in self.records if r["req"] is not None and r["req"].error is not None),
            "clients_alive": sum(t.is_alive() for t in self.threads),
        }
        return self.measured

    def end_to_end(self):
        m = self.measured
        late = (WAIT_S + 60.0) * 1e3  # a request that was refused or never answered misses every limit
        ttft = [(r["first_token"] - r["submit"]) * 1e3 if r["ok"] and r["first_token"] else late
                for r in m["sent"]]
        metrics = {
            "serve_tokens_per_s": m["tokens_emitted"] / m["seconds"],
            "ttft_mean_ms": sum(ttft) / len(ttft),
            "ttft_p95_ms": G.percentile(ttft, 0.95),
            # the token-gap tail: the mean of the gaps from the 90th to the 99th percentile by rank
            "itl_tail_ms": G.band_mean([g[0] for g in m["gaps"]]) * 1e3,
        }
        return metrics, len(m["sent"]), len(m["sent"]) - len(m["done"])

    def dump(self):
        """The window's labelled gaps and flight records, for the look at a tail (``--dump 1``; not a result)."""
        m = self.measured
        return {"seconds": m["seconds"], "sent": len(m["sent"]), "tokens_emitted": m["tokens_emitted"],
                "timeline": self.timeline,
                "gaps": [[g * 1e3, end - m["t0"], label] for g, end, label in m["gaps"]],
                "flight": [{**r, "t": r["t"] - m["t0"]} for r in m["flight"]]}

    def release(self):
        self.sched.stop()
        self.sched = self.engine = self.registry = None
        for r in self.records:
            r["req"] = None
        gc.collect()

    # ------------------------------------------------------------ correct
    def _sample(self, done):
        """The longest finished request of the window and a few more, drawn from the seed."""
        done = sorted(done, key=lambda r: (r["submit"], r["client"]))
        self.lengths_wrong = 0
        if not done:
            return []
        longest = max(done, key=lambda r: r["prompt_len"] + r["n_out"])
        rest = [r for r in done if r is not longest]
        random.Random(self.seed).shuffle(rest)
        picked = [longest] + rest[:self.cell["check"]["sample"] - 1]
        eos = 256  # the byte tokenizer's end of text: an answer may stop there
        self.lengths_wrong = sum(
            1 for r in done if not (r["n_out"] == r["max_new"] or (r["n_out"] < r["max_new"] and r["last"] == eos)))
        return [(list(r["req"].tokens), list(r["req"].result)) for r in picked]

    def check(self, probe=False):
        limits, m = self.cell["correct"], self.measured
        readings = {n: m[n] for n in ("compiles_in_window", "fault_counters_moved", "admitted_not_answered",
                                      "clients_alive")}
        readings["answers_wrong_length"] = self.lengths_wrong
        readings["answers_checked_min"] = -len(self.sample)  # the sample has to be there to check
        extra = {"timeline": self.timeline}
        if self.sample:
            gaps, cgaps = R.served_gaps(self.spec, self.seed, self.sample, self.store_dtype,
                                        control="fp8" if probe else None, row_block=self.cell["check"].get("row_block", 8))
            readings["served_gap_max"], readings["served_gap_mean"] = float(gaps.max()), float(gaps.mean())
            extra.update(served_tokens_checked=int(gaps.size), served_tokens_off_best=int((gaps > 0).sum()))
            if cgaps is not None:
                extra["probe"] = {"control_fp8": {"served_gap_max": float(cgaps.max()),
                                                  "served_gap_mean": float(cgaps.mean()),
                                                  "tokens_off_best": int((cgaps > 0).sum())}}
        compared = {n: {"value": v, "limit": limits[n], "ok": bool(v <= limits[n])}
                    for n, v in readings.items() if n in limits}
        ok = all(c["ok"] for c in compared.values()) and "served_gap_max" in compared
        return ok, compared, extra
