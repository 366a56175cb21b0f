"""serve_closed_sarvam: ``serve_closed_cohere2``'s closed loop for ``arch:
sarvam_mla`` and the ``shareddocs`` mixes. What differs from the parent class:

- the weights come from ``lib/weights_sarvam_mla.py`` and the reference from
  ``lib/reference_sarvam_mla.py`` (the up-projected order only, one sequence
  and one layer's weights at a time, a segment of tokens at a time);
- the traffic is ``lib/traffic_shareddocs.py``'s: every document goes once
  through the engine's own path in set-up (prefilled in chunks, committed to
  the prefix cache) and is then asked by SEVERAL clients, so two live slots
  read one document's pages;
- the sample that decides ``correct`` holds the cell's ``check.long`` longest
  finished sessions and, besides, requests drawn from the seed; it must hold
  tokens decoded at a context over ``check.long_context`` and a question that
  was prefilled over a document's pages while another client of the same
  document was live.

``fault`` in the cell file (the planted faults of ``benchmarks/tests``) is
honoured at rehearsal size only: ``served_token`` (one served token altered
after the window), ``router_bias_zeroed`` (the program is given a bias of
noughts: it chooses by the scores alone), ``shared_page_overwritten`` (the
first page of every committed document is overwritten before the clients
start), and the
reference's own ``no_latent_norm`` / ``scale_without_mscale`` (the equations
are computed without the step, which reads as the program doing one step too
many: the same distance, from the other side)."""

import gc
import importlib
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import common, serve_closed, serve_closed_cohere2
from benchmarks.lib import reference_sarvam_mla as R
from benchmarks.lib import weights_sarvam_mla as WS

PROGRAM_FAULTS = ("served_token", "router_bias_zeroed", "shared_page_overwritten")


class Cell(serve_closed_cohere2.Cell):
    def __init__(self, env):
        super().__init__(env)
        self.rehearse = bool(env.get("rehearse"))
        if self.fault and self.fault not in PROGRAM_FAULTS + R.FAULTS:
            raise ValueError(f"unknown planted fault {self.fault!r}")

    def setup(self):
        from trlx_tpu import telemetry
        from trlx_tpu.models.transformer import ArchFlags
        from trlx_tpu.serve import InferenceEngine, ServeConfig
        from trlx_tpu.serve.slots import SlotScheduler
        from trlx_tpu.supervisor import monotonic

        self.clock = monotonic  # the one clock the serve engine stamps its traces with
        telemetry.start()
        config = common.trl_config(
            self.spec, self.cell["model"], self.cell.get("train", {}),
            {"gen_kwargs": {"do_sample": not self.mix.get("greedy", True)}}, self.seed)
        serve = ServeConfig.from_dict(self.cell["serve"])
        ArchFlags.for_spec(config.model.resolve_spec())  # a program that lacks the arch raises here: no weight is made

        def make():  # the engine owns what it makes: the stacked trunk is released as it is split by layer
            params = WS.hydra_weights(self.spec, self.seed, self.k, self.store_dtype)
            if self.fault == "router_bias_zeroed":
                moe = params["trainable"]["blocks"]["moe"]
                moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
            self.say("weights made from the seed")
            return jax.block_until_ready(params)

        self.engine = InferenceEngine(config, serve=serve, params=make)
        self.say("engine built")
        self.sched = SlotScheduler(self.engine)
        if self.rehearse and self.store_dtype == jnp.float32:
            # a rehearsal computes in float32 and keeps its pages so too (before any program is compiled): what is
            # left between program and reference is then the order of float32 sums, and a planted fault stands out
            # at once. With bfloat16 pages the rounding of one latent now and then sends a token to another expert,
            # which moves a logit of this tiny model by up to 5: a sound rehearsal read like a faulty one in one
            # run of five (the served cell keeps bfloat16 pages and its limits are placed on the chip)
            rt = self.sched.runtime
            rt.pool = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), rt.pool)
        self.warmup_s = self.sched.warmup()
        self.say(f"warmed: { {k: round(v, 1) for k, v in self.warmup_s.items()} }")
        rt, e = self.sched.runtime, self.engine
        self.decode_scopes = serve_closed_cohere2.op_scopes(rt._decode_fn().compiled_for(
            e.blocks, e.embed, e.ln_f, rt.pool, rt.state, np.int32(0)).as_text())
        self.sched.start()
        self.registry = telemetry.current().registry
        generator = importlib.import_module(f"benchmarks.lib.{self.mix['generator']}")
        documents, clients = generator.serve_requests(self.mix, self.seed)
        self.n_docs = self.n_long = len(documents)
        # every document once through the engine's own path: chunked prefill, committed to the prefix cache
        started = time.perf_counter()
        pending = [self.sched.submit(doc, max_new_tokens=1) for doc in documents]
        for req in pending:
            if not req.done.wait(timeout=serve_closed.WAIT_S * 4) or req.error is not None:
                raise RuntimeError(f"a document was not committed in set-up: {req.error!r}")
        chunks = self.registry.counters.get("serve/prefill_chunks", 0)
        self.say(f"{len(documents)} documents committed ({sum(map(len, documents))} tokens, {chunks} chunks) "
                 f"in {time.perf_counter() - started:.1f} s")
        if self.fault == "shared_page_overwritten":  # the first page of every document, in every layer
            rt = self.sched.runtime
            for doc in documents:
                pages = self.sched.cache.match(list(doc))
                self.sched.cache.release_all(pages)
                rt.pool = jax.tree_util.tree_map(lambda leaf: leaf.at[pages[0]].set(1.0), rt.pool)
        self.threads = [
            threading.Thread(target=self._client, args=(i, reqs), name=f"bench-client-{i}", daemon=True)
            for i, reqs in enumerate(clients)
        ]
        gc.collect()
        gc.freeze()  # set-up's heap leaves the collector's sight; ``release`` gives it back
        for t in self.threads:
            t.start()
        time.sleep(self.mix.get("ramp_s", 3.0))

    def _sample(self, done):
        """The ``check.long`` longest finished sessions, and requests drawn from the seed besides."""
        done = sorted(done, key=lambda r: (r["submit"], r["client"]))
        eos = 256
        self.lengths_wrong = sum(
            1 for r in done if not (r["n_out"] == r["max_new"] or (r["n_out"] < r["max_new"] and r["last"] == eos)))
        check = self.cell["check"]
        longest = sorted(done, key=lambda r: -(r["prompt_len"] + r["n_out"]))[:check["long"]]
        rest = [r for r in done if not any(r is x for x in longest)]
        random.Random(self.seed).shuffle(rest)
        picked = longest + rest[:check["sample"] - len(longest)]
        self.long_checked = sum(1 for r in picked if r["prompt_len"] > check["long_context"])
        self.decoded_over_long = sum(r["n_out"] for r in picked if r["prompt_len"] > check["long_context"])
        # a question prefilled over a document's pages while another client of that document was live
        answered = [r for r in self.records if r["ok"]]
        self.shared_prefills = sum(
            1 for r in picked if r["req"].trace.prefix_blocks_hit and any(
                q["client"] != r["client"] and q["client"] % self.n_docs == r["client"] % self.n_docs
                and q["admitted"] <= r["admitted"] < q["harvested"] for q in answered))
        self.behind_window = 0
        return [(list(r["req"].tokens), list(r["req"].result)) for r in picked]

    def check(self, probe=False):
        limits, m = self.cell["correct"], self.measured
        readings = {n: m[n] for n in ("compiles_in_window", "fault_counters_moved", "admitted_not_answered",
                                      "clients_alive")}
        readings["answers_wrong_length"] = self.lengths_wrong
        readings["answers_checked_min"] = -len(self.sample)  # the sample has to be there to check
        readings["long_sessions_checked_min"] = -self.long_checked
        readings["tokens_decoded_over_long_context_min"] = -min(self.decoded_over_long, 1)
        readings["shared_prefills_checked_min"] = -min(self.shared_prefills, 1)
        extra = {"timeline": self.timeline}
        if self.sample:
            gaps, cgaps = R.served_gaps(
                self.spec, self.seed, self._faulted(self.sample), self.store_dtype,
                control="fp8" if probe else None, pad_to=self.cell["check"].get("pad_to", 4096),
                fault=self.fault if self.fault in R.FAULTS else None)
            readings["served_gap_max"], readings["served_gap_mean"] = float(gaps.max()), float(gaps.mean())
            readings["served_off_best_share"] = float((gaps > 0).mean())
            extra.update(served_tokens_checked=int(gaps.size), served_tokens_off_best=int((gaps > 0).sum()),
                         shared_prefills_checked=self.shared_prefills, long_sessions_checked=self.long_checked)
            if cgaps is not None:
                extra["probe"] = {"control_fp8": {"served_gap_max": float(cgaps.max()),
                                                  "served_gap_mean": float(cgaps.mean()),
                                                  "served_off_best_share": float((cgaps > 0).mean()),
                                                  "tokens_off_best": int((cgaps > 0).sum())}}
        compared = {n: {"value": v, "limit": limits[n], "ok": bool(v <= limits[n])}
                    for n, v in readings.items() if n in limits}
        ok = all(c["ok"] for c in compared.values()) and "served_gap_max" in compared
        return ok, compared, extra
