"""The sampler thread's decisions, driven with a fake registry and clock."""

from benchmark.harness.sampler import RegistrySampler, SamplerPolicy


class FakeRegistry:
    def __init__(self):
        self.values = {"train_grads_committed": None, "train_rounds_total": 0.0,
                       "train_loss": None, "compile_cache_requests_total": 3.0,
                       "compile_cache_hits_total": 3.0}

    def value(self, name):
        return self.values[name]

    def boundary(self, rounds, loss):
        self.values.update(train_rounds_total=float(rounds), train_loss=loss,
                           train_grads_committed=float(rounds))


def drive(policy, boundaries, until=100.0, step=0.5):
    """Boundaries are ``(time, rounds, loss)``; returns the sampler's log and
    the times at which it asked for the stop."""
    reg, stops = FakeRegistry(), []
    sampler = RegistrySampler(reg, policy, stop=lambda: stops.append(now))
    pending = sorted(boundaries)
    now = 0.0
    while now < until:
        while pending and pending[0][0] <= now:
            _, rounds, loss = pending.pop(0)
            reg.boundary(rounds, loss)
        if sampler.poll(now):
            break
        now += step
    return sampler.log, stops


def test_window_opens_at_the_first_boundary_after_the_warmup_and_stops_after_seconds():
    bounds = [(float(t), 10 * t, 10.0 - 0.1 * t) for t in range(1, 40)]
    log, stops = drive(SamplerPolicy(warmup_rounds=20, seconds=5.0, ref_round=40), bounds)
    assert log.t_open == 2.0  # round 20
    assert stops == [7.0]  # 5 s later; round 40 long behind
    assert [b.rounds for b in log.boundaries][:3] == [10.0, 20.0, 30.0]
    assert log.boundaries[1].loss == 9.8


def test_no_stop_before_the_ref_round():
    bounds = [(float(t), 10 * t, 5.0) for t in range(1, 40)]
    log, stops = drive(SamplerPolicy(warmup_rounds=10, seconds=2.0, ref_round=100), bounds)
    assert log.t_open == 1.0
    assert stops == [10.0]  # the boundary of round 100, not 2 s after the opening


def test_no_boundary_no_stop():
    log, stops = drive(SamplerPolicy(10, 1.0, 10), [], until=10.0)
    assert log.t_open is None and stops == [] and log.boundaries == []
