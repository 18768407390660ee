"""HTTP front end + serving loop.

The fast tests drive ThreadingHTTPServer + ServingLoop over StubEngine
(tier-1: no programs compile). The slow test is the full stack — real
tiny model, compiled bucket programs, two CONCURRENT generate requests
sharing the continuous-batching scheduler.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from acco_tpu.serve.engine import StubEngine
from acco_tpu.serve.scheduler import ContinuousBatchingScheduler, GenRequest
from acco_tpu.serve.server import ServingLoop, encode_prompt, serve_http


class FakeTokenizer:
    eos_token_id = 0

    def __call__(self, text, **kw):
        return {"input_ids": [ord(c) % 32 for c in text]}

    def decode(self, ids):
        return "".join(chr(65 + (i % 26)) for i in ids)


def _post(port, payload, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture
def stub_server():
    eng = StubEngine(max_slots=2, num_pages=32)
    sched = ContinuousBatchingScheduler(eng)
    loop = ServingLoop(sched).start()
    httpd = serve_http(loop, FakeTokenizer(), host="127.0.0.1", port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        yield httpd.server_address[1], eng
    finally:
        httpd.shutdown()
        httpd.server_close()
        loop.stop()


def test_generate_with_tokens_and_healthz(stub_server):
    port, _ = stub_server
    status, out = _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 4})
    assert status == 200
    assert out["tokens"] == [4, 5, 6, 7]
    assert out["n_generated"] == 4
    assert out["finish_reason"] == "length"
    status, health = _get(port, "/healthz")
    assert status == 200 and health["ok"]
    assert health["completed"] == 1


def test_generate_with_prompt_string(stub_server):
    port, _ = stub_server
    status, out = _post(port, {"prompt": "ab", "max_new_tokens": 2})
    assert status == 200
    # FakeTokenizer: 'ab' -> [1, 2]; stub model continues 3, 4
    assert out["tokens"] == [3, 4]
    assert out["text"] == "DE"


def test_concurrent_requests_share_the_batch(stub_server):
    port, eng = stub_server
    results = {}

    def hit(name, start):
        results[name] = _post(
            port, {"tokens": [start], "max_new_tokens": 8}
        )

    threads = [
        threading.Thread(target=hit, args=(f"r{i}", 10 + i))
        for i in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for i in range(3):
        status, out = results[f"r{i}"]
        assert status == 200
        assert out["tokens"] == [10 + i + k for k in range(1, 9)]


def test_bad_requests(stub_server):
    port, _ = stub_server
    for payload, want in ((
        {"tokens": []}, 400), ({}, 400),
    ):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(payload).encode(),
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == want
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=10)
    assert e.value.code == 404


# -- resilience: validation / shedding / deadlines / drain (ISSUE 20) -------


def _post_raw(port, payload, timeout=30):
    """POST that returns (status, body, headers) without raising on 4xx/5xx."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _wait_until(predicate, what, timeout_s=20.0):
    """Bounded wait on the scheduler's own state. A sleep that stands for
    "the request is active by now" is a guess the loaded tier-1 machine
    breaks; the state says when."""
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, f"never happened: {what}"
        time.sleep(0.005)


def _active(sched) -> int:
    return sum(r is not None for r in sched.slots)


def _long_context_engine(**kw):
    """A stub whose context holds 512 tokens (the default holds 16), so a
    request can be sized to miss a budget by a factor of ten, not two."""
    return StubEngine(num_pages=160, max_pages_per_seq=128, **kw)


@contextlib.contextmanager
def _server(engine=None, request_timeout_s=30.0, **sched_kw):
    eng = engine or StubEngine(max_slots=2, num_pages=32)
    sched = ContinuousBatchingScheduler(eng, **sched_kw)
    loop = ServingLoop(sched).start()
    httpd = serve_http(
        loop, FakeTokenizer(), host="127.0.0.1", port=0,
        request_timeout_s=request_timeout_s,
    )
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        yield httpd.server_address[1], sched, loop
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=10)
        loop.stop()


def test_generate_input_validation_400s(stub_server):
    port, _ = stub_server
    cases = [
        {"tokens": [1], "max_new_tokens": 0},
        {"tokens": [1], "max_new_tokens": -5},
        {"tokens": [1], "max_new_tokens": 10_000},  # > max_context
        {"tokens": [1], "max_new_tokens": "lots"},
        {"tokens": [1], "top_k": -1},
        {"tokens": [1], "temperature": float("inf")},
        {"tokens": [1], "temperature": float("nan")},
        {"tokens": [1], "deadline_ms": -100},
        {"tokens": [1], "deadline_ms": 0},
        {"tokens": ["a", "b"]},  # non-integer tokens
        {"tokens": list(range(64))},  # longer than the largest bucket
    ]
    for payload in cases:
        status, body, _ = _post_raw(port, payload)
        assert status == 400, f"{payload} -> {status} {body}"
        assert body["error"], payload
    # validation rejections never reached the scheduler queue
    status, health = _get(port, "/healthz")
    assert health["waiting"] == 0 and health["active"] == 0


def test_shed_queue_full_gets_429_with_retry_after():
    eng = _long_context_engine(max_slots=1, decode_sleep_s=0.02)
    with _server(engine=eng, max_waiting=1, retry_after_s=3.0) as (
        port, sched, loop,
    ):
        results = []

        def hit(max_new_tokens):
            results.append(_post_raw(
                port, {"tokens": [1], "max_new_tokens": max_new_tokens}
            ))

        # 1 active + 1 waiting (queue full) + 1 shed, each arriving only
        # once the scheduler holds the one before it. The first decodes
        # for ~2 s (100 steps of 20 ms): two posts on the loopback fit in
        # that many hundred times over.
        threads = [
            threading.Thread(target=hit, args=(n,)) for n in (100, 2, 2)
        ]
        threads[0].start()
        _wait_until(lambda: _active(sched) == 1, "first request active")
        threads[1].start()
        _wait_until(lambda: len(sched.waiting) == 1, "second request queued")
        threads[2].start()
        for t in threads:
            t.join(timeout=60)
        statuses = sorted(s for s, _, _ in results)
        assert statuses == [200, 200, 429], statuses
        shed = next(r for r in results if r[0] == 429)
        assert shed[1]["kind"] == "queue_full"
        assert int(shed[2]["Retry-After"]) == 3
        assert sched.allocator.in_use == 0


def test_zombie_timeout_cancels_and_frees_pages():
    """The 504 path must CANCEL the request in the scheduler — before
    ISSUE 20 the handler returned and the scheduler decoded a zombie to
    completion with its pages held."""
    # 500 steps of 10 ms against a 0.5 s budget: missed tenfold. The
    # fresh request at the end needs 2 steps: inside the budget 25 times.
    eng = _long_context_engine(max_slots=2, decode_sleep_s=0.01)
    with _server(engine=eng, request_timeout_s=0.5) as (port, sched, loop):
        status, body, _ = _post_raw(
            port, {"tokens": [1], "max_new_tokens": 500}, timeout=30
        )
        assert status == 504 and "timed out" in body["error"]
        # regression lever: every page back in the free pool, no zombie
        # decode left running
        _wait_until(lambda: sched.allocator.in_use == 0, "pages freed")
        assert all(s is None for s in sched.slots)
        assert sched.cancelled == 1
        # and the loop still serves fresh work afterwards
        status, out, _ = _post_raw(
            port, {"tokens": [7], "max_new_tokens": 2}, timeout=30
        )
        assert status == 200 and out["tokens"] == [8, 9]


def test_client_deadline_maps_to_504_deadline():
    eng = StubEngine(max_slots=2, num_pages=32, decode_sleep_s=0.02)
    with _server(engine=eng) as (port, sched, loop):
        status, body, _ = _post_raw(
            port,
            {"tokens": [1], "max_new_tokens": 12, "deadline_ms": 60},
            timeout=30,
        )
        assert status == 504 and "deadline" in body["error"]
        assert sched.allocator.in_use == 0


def test_healthz_degraded_before_dead():
    with _server(max_waiting=1) as (port, sched, loop):
        status, health = _get(port, "/healthz")
        assert status == 200 and health["state"] == "ok" and health["ok"]
        # park a request in the queue without running the loop: stop it
        # first so the queue depth is observable, not racy
        loop.stop()
        sched.submit(GenRequest(prompt=[1], max_new_tokens=4))
        h = loop.health()
        assert h["state"] == "degraded" and not h["ok"]


def test_drain_endpoint_finishes_in_flight_then_stops():
    eng = StubEngine(max_slots=2, num_pages=32, decode_sleep_s=0.01)
    with _server(engine=eng) as (port, sched, loop):
        results = []

        def hit():
            results.append(_post_raw(
                port, {"tokens": [3], "max_new_tokens": 8}, timeout=30
            ))

        t = threading.Thread(target=hit)
        t.start()
        _wait_until(lambda: _active(sched) == 1, "request in flight")
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/admin/drain",
            data=json.dumps({"budget_s": 10}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            drain = json.loads(resp.read())
        t.join(timeout=30)
        assert drain["drained"] and drain["in_budget"]
        assert drain["cancelled"] == 0
        # the in-flight request finished normally during the drain
        status, out, _ = results[0]
        assert status == 200 and out["tokens"] == [4 + k for k in range(8)]
        # new work is shed with 503 + draining
        status, body, headers = _post_raw(
            port, {"tokens": [1], "max_new_tokens": 2}
        )
        assert status == 503 and body["kind"] == "draining"
        assert "Retry-After" in headers
        # healthz reports draining as not-ready
        try:
            status, health = _get(port, "/healthz")
        except urllib.error.HTTPError as e:
            status, health = e.code, json.loads(e.read())
        assert status == 503 and health["state"] == "draining"
        assert not loop._thread.is_alive()
        assert sched.allocator.in_use == 0


def test_drain_cancels_stragglers_over_budget():
    eng = _long_context_engine(max_slots=2, decode_sleep_s=0.01)
    with _server(engine=eng) as (port, sched, loop):
        results = []

        def hit():
            results.append(_post_raw(
                port, {"tokens": [3], "max_new_tokens": 500}, timeout=30
            ))

        t = threading.Thread(target=hit)
        t.start()
        _wait_until(lambda: _active(sched) == 1, "request in flight")
        summary = loop.drain(budget_s=0.2)  # a 25th of the ~5 s of decode
        t.join(timeout=30)
        assert summary["drained"] and not summary["in_budget"]
        assert summary["cancelled"] == 1
        status, body, _ = results[0]
        assert status == 503 and "drain" in body["error"]
        assert sched.allocator.in_use == 0


def test_stop_is_idempotent_and_raises_on_wedged_thread():
    sched = ContinuousBatchingScheduler(StubEngine())
    loop = ServingLoop(sched)
    loop.stop()  # never started: no-op
    loop = ServingLoop(sched).start()
    loop.stop()
    loop.stop()  # already exited: no-op
    assert not loop._thread.is_alive()
    # a thread that refuses to die must raise, not silently leak
    wedged = ServingLoop(sched)
    wedged._thread = threading.Thread(
        target=lambda: time.sleep(3600), daemon=True
    )  # lint: thread-ok (simulated wedge; never joinable by design)
    wedged._thread.start()
    with pytest.raises(RuntimeError, match="did not exit"):
        wedged.stop(timeout=0.2)


def test_encode_prompt_normalizes_batched_tokenizers():
    from acco_tpu.data.tokenizer import ByteTokenizer

    assert encode_prompt(ByteTokenizer(), "hi") == [104, 105]
    assert encode_prompt(FakeTokenizer(), "ab") == [1, 2]


@pytest.mark.slow
def test_end_to_end_real_engine_two_concurrent():
    """Full stack: tiny Llama, compiled bucket programs, two concurrent
    HTTP generations through the continuous-batching scheduler."""
    import os

    import jax
    import yaml

    import jax.numpy as jnp

    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.models.registry import build_model
    from acco_tpu.serve.engine import ServeEngine

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo_root, "config", "model", "tiny.yaml")) as f:
        model_cfg = yaml.safe_load(f)
    model = build_model(model_cfg, repo_root=repo_root, param_dtype=jnp.float32)
    engine = ServeEngine(
        model, page_size=8, num_pages=32, max_pages_per_seq=8,
        max_slots=2, cache_dtype="float32",
    )
    engine.set_params(model.init(jax.random.PRNGKey(0)))
    sched = ContinuousBatchingScheduler(engine)
    loop = ServingLoop(sched).start()
    httpd = serve_http(loop, ByteTokenizer(), host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        results = {}

        def hit(name, prompt):
            results[name] = _post(
                port,
                {"prompt": prompt, "max_new_tokens": 6, "temperature": 0.0},
                timeout=120,
            )

        threads = [
            threading.Thread(target=hit, args=("a", "hello")),
            threading.Thread(target=hit, args=("b", "world!")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        for name in ("a", "b"):
            status, out = results[name]
            assert status == 200
            assert out["n_generated"] == 6
            assert out["finish_reason"] in ("length", "stop")
        status, health = _get(port, "/healthz")
        assert health["completed"] == 2
        assert health["decode_steps"] > 0
    finally:
        httpd.shutdown()
        httpd.server_close()
        loop.stop()
