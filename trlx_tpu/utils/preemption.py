"""Preemption-safe training: trap SIGTERM during learn() and checkpoint
before exiting.

TPU pods under batch schedulers (GKE node drains, spot/preemptible VMs,
SLURM) deliver SIGTERM ahead of eviction. The reference has no preemption
story — its checkpointing is configured but never invoked from either
learn loop (reference trlx/model/__init__.py:101-129, SURVEY quirks).
Here the trainers' learn loops poll a signal-set flag at step boundaries
(a dispatched XLA step cannot be interrupted mid-flight anyway), save the
normal component checkpoint, and return cleanly; the run then resumes
bit-exact via ``config.train.resume_from``
(tests/test_checkpoint.py::test_sigterm_preemption_saves_and_resumes).
"""

import signal
import threading


class PreemptionGuard:
    """Context manager that records SIGTERM instead of dying.

    Only the main thread may install signal handlers (a Python
    restriction); constructed anywhere else — or with ``enabled=False``
    (``train.save_on_preemption: false``) — the guard is inert and
    ``requested`` stays False. The previous handler is restored on exit,
    so the trap is scoped to the learn loop.
    """

    def __init__(self, enabled: bool = True, poll_interval: int = 1):
        self.requested = False
        self._enabled = enabled
        self._prev = None
        self._installed = False
        # Cross-process agreement runs a collective and a host sync; doing
        # that EVERY step puts a pipeline bubble into small-model steps
        # (gpt2-124M: 29 ms a step on a v5e — chip_smoke.py). Callers pass a deterministic interval
        # (trainers use min(train.log_interval, 8) — capped so worst-case
        # detection lag stays within eviction grace windows) so all ranks
        # hit the allgather at the same boundaries and skip it in between.
        self._poll_interval = max(1, int(poll_interval))
        self._polls = 0

    def _on_signal(self, signum, frame):
        self.requested = True
        # plain dict increment — safe inside a signal handler, and makes
        # the eviction visible in the metrics stream (fault/* counters)
        from trlx_tpu import telemetry

        telemetry.inc("fault/preempt_sigterm")

    def poll(self, extra: bool = False) -> bool:
        """The stop flag AGREED across JAX processes: any rank's SIGTERM
        (or locally-raised ``extra`` condition) stops every rank.

        A node drain signals hosts at different times (or only one); a
        rank acting alone would exit mid-collective — deadlocking the
        survivors — and, off process 0, its save() is a gated no-op, so
        nothing would be written at all. Every rank calls poll() at the
        same step boundaries, so the tiny allgather is itself a safe
        collective — and it only actually runs every ``poll_interval``-th
        call (the call COUNT is rank-deterministic, so ranks agree on which
        boundaries are collective ones; between them poll() returns False
        even if the local flag is set, because a rank acting on local state
        alone is exactly the deadlock this method exists to prevent).
        Single-process: just the local flags, every call.

        ``extra`` folds additional rank-local stop conditions into the
        same agreement — the run supervisor's walltime deadline and stall
        escalation ride it (trlx_tpu.supervisor), so e.g. one rank
        crossing ``train.max_walltime`` a moment before the others still
        makes every rank exit together at the same boundary."""
        import jax

        local = self.requested or bool(extra)
        if jax.process_count() == 1:
            return local
        self._polls += 1
        if (self._polls - 1) % self._poll_interval:
            return False
        import numpy as np
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([1.0 if local else 0.0], np.float32)
        )
        return bool(np.asarray(flags).max() > 0)

    def __enter__(self) -> "PreemptionGuard":
        if (
            self._enabled
            and threading.current_thread() is threading.main_thread()
        ):
            self._prev = signal.getsignal(signal.SIGTERM)
            signal.signal(signal.SIGTERM, self._on_signal)
            self._installed = True
        return self

    def __exit__(self, *exc) -> bool:
        """Restore the previous SIGTERM disposition.

        Embedder caveat: ``signal.getsignal()`` returns ``None`` for a
        handler installed at the C level (outside the Python signal
        module — e.g. by a host application or an extension library), and
        such a handler CANNOT be re-installed from Python. After
        ``learn()`` returns, a C-level previous handler is therefore
        replaced by ``SIG_DFL`` rather than left as this guard's
        recording handler — nobody polls the flag anymore, and a
        swallowed SIGTERM would make the process undrainable. A host
        application that installed its own C-level SIGTERM handler must
        reinstall it after ``learn()`` returns."""
        if self._installed:
            signal.signal(
                signal.SIGTERM,
                self._prev if self._prev is not None else signal.SIG_DFL,
            )
            self._installed = False
        return False
