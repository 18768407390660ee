"""A host-only thread that samples the program's metrics registry.

The registry's gauges (``train_loss``, ``train_grads_committed``) keep only the
last logging boundary's value, so the benchmark reads them from its own thread
while the trainer runs. The thread also decides when the run ends: once the
window has been open for the asked seconds and the cell's ``ref_round`` is
behind, it sends this process the SIGTERM a preempted user's job would get,
and the trainer stops at a round boundary by its own preemption path.

No JAX here: a few dictionary reads under the registry's lock per poll.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from dataclasses import dataclass, field


@dataclass
class BoundarySample:
    t_epoch: float  # when the sampler saw the boundary
    rounds: float  # train_rounds_total then (the boundary's round, or one more)
    loss: float
    committed: float
    cache_requests: float
    cache_hits: float


@dataclass
class SamplerPolicy:
    warmup_rounds: int
    seconds: float
    ref_round: int
    poll_s: float = 0.002


@dataclass
class SamplerLog:
    boundaries: list = field(default_factory=list)
    t_open: float | None = None  # epoch at which the window opened
    t_stop: float | None = None  # epoch at which the stop was requested


def request_stop() -> None:
    """What a preemption sends."""
    os.kill(os.getpid(), signal.SIGTERM)


class RegistrySampler(threading.Thread):
    def __init__(self, registry, policy: SamplerPolicy, stop=request_stop) -> None:
        super().__init__(name="bench-sampler", daemon=True)
        self.registry = registry
        self.policy = policy
        self.log = SamplerLog()
        self._stop_action = stop
        self._halt = threading.Event()

    def halt(self) -> None:
        self._halt.set()

    def _value(self, name: str) -> float:
        v = self.registry.value(name)
        return float("nan") if v is None else float(v)

    def poll(self, now: float) -> bool:
        """One poll; returns True once the stop has been requested. Split
        from ``run`` so that a test can drive it with a fake clock."""
        log, policy = self.log, self.policy
        committed = self.registry.value("train_grads_committed")
        last = log.boundaries[-1].committed if log.boundaries else None
        if committed is not None and committed != last:
            # train_loss is emitted before train_grads_committed, so the
            # loss read now is this boundary's
            sample = BoundarySample(
                t_epoch=now,
                rounds=self._value("train_rounds_total"),
                loss=self._value("train_loss"),
                committed=float(committed),
                cache_requests=self._value("compile_cache_requests_total"),
                cache_hits=self._value("compile_cache_hits_total"),
            )
            log.boundaries.append(sample)
            if log.t_open is None and sample.rounds >= policy.warmup_rounds:
                log.t_open = now
        if (
            log.t_open is not None
            and now - log.t_open >= policy.seconds
            and log.boundaries[-1].rounds >= policy.ref_round
        ):
            log.t_stop = now
            self._stop_action()
            return True
        return False

    def run(self) -> None:
        while not self._halt.is_set():
            if self.poll(time.time()):
                return
            time.sleep(self.policy.poll_s)
