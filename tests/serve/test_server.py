"""The asyncio HTTP front door: routing, status↔taxonomy mapping,
deadline propagation from socket-in (the PR 9 batcher deadline tests,
now through the socket path), 429 shedding, drain, recompile flatness."""

import asyncio
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.resilience import faults
from hyperspace_tpu.serve.batcher import RequestBatcher
from hyperspace_tpu.serve.engine import QueryEngine
from hyperspace_tpu.serve.server import HttpFrontDoor
from hyperspace_tpu.telemetry import registry as telem


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(1)
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((256, 4)) * 0.3, jnp.float32)))
    eng = QueryEngine(table, ("poincare", 1.0))
    eng.topk_neighbors(np.zeros(8, np.int32), 4)  # warm (8, 4)
    return eng


async def _request(host, port, method, path, payload=None, raw=None,
                   keep_alive=False, rw=None):
    """(status, parsed body[, (reader, writer)]): one HTTP round trip.
    ``rw`` reuses a keep-alive connection; ``keep_alive`` keeps it."""
    if rw is None:
        reader, writer = await asyncio.open_connection(host, port)
    else:
        reader, writer = rw
    body = (raw if raw is not None
            else b"" if payload is None
            else json.dumps(payload).encode())
    conn = "keep-alive" if keep_alive else "close"
    writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n"
                  f"Connection: {conn}\r\n\r\n").encode() + body)
    await writer.drain()
    status_line = await reader.readline()
    status = int(status_line.split()[1])
    clen = 0
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        name, _, val = h.decode().partition(":")
        if name.strip().lower() == "content-length":
            clen = int(val)
    resp = json.loads((await reader.readexactly(clen)).decode())
    if keep_alive:
        return status, resp, (reader, writer)
    writer.close()
    return status, resp


def _door(engine, **kw):
    bat_kw = {k: kw.pop(k) for k in ("queue_max", "deadline_ms",
                                     "cache_size", "ladder_down_after")
              if k in kw}
    bat = RequestBatcher(engine, min_bucket=8, max_bucket=64,
                         cache_size=bat_kw.pop("cache_size", 0),
                         **bat_kw)
    return HttpFrontDoor(bat, **kw), bat


def _run(engine, coro_fn, **kw):
    """Start a door, run the test coroutine against it, drain."""
    door, bat = _door(engine, **kw)

    async def main():
        await door.start()
        try:
            return await coro_fn(door, bat)
        finally:
            await door.drain()

    return asyncio.run(main()), door


def test_topk_score_stats_healthz_round_trip(engine):
    async def go(door, bat):
        h, p = door.host, door.port
        out = {}
        out["topk"] = await _request(h, p, "POST", "/v1/topk",
                                     {"ids": [1, 2, 3], "k": 4})
        out["score"] = await _request(h, p, "POST", "/v1/score",
                                      {"u": [0, 1], "v": [2, 3],
                                       "prob": True})
        out["stats"] = await _request(h, p, "GET", "/v1/stats")
        out["health"] = await _request(h, p, "GET", "/healthz")
        return out

    out, door = _run(engine, go)
    status, r = out["topk"]
    assert status == 200
    ref_i, ref_d = (np.asarray(a) for a in engine.topk_neighbors(
        np.asarray([1, 2, 3], np.int32), 4))
    np.testing.assert_array_equal(np.asarray(r["neighbors"]), ref_i)
    np.testing.assert_array_equal(
        np.asarray(r["dists"], np.float32).view(np.uint32),
        ref_d.astype(np.float32).view(np.uint32))
    status, r = out["score"]
    assert status == 200 and len(r["scores"]) == 2
    assert all(0.0 <= s <= 1.0 for s in r["scores"])  # prob=True
    status, r = out["stats"]
    assert status == 200
    assert r["server"]["draining"] is False
    assert "recompiles" in r and "scan_strategy" in r
    status, r = out["health"]
    assert status == 200 and r["ok"] is True
    assert door.served == 4


def test_error_taxonomy_maps_to_status_codes(engine):
    """parse/validation → 400, unknown route → 404, wrong method →
    405, deadline → 504; every request answers exactly one typed
    response and the server keeps serving."""
    async def go(door, bat):
        h, p = door.host, door.port
        rows = [
            await _request(h, p, "POST", "/v1/topk",
                           raw=b"this is not json"),
            await _request(h, p, "POST", "/v1/topk",
                           {"ids": [0.5], "k": 4}),
            await _request(h, p, "POST", "/v1/topk",
                           {"ids": [1], "k": 4, "deadline_ms": "soon"}),
            await _request(h, p, "POST", "/v1/nope", {}),
            await _request(h, p, "GET", "/v1/topk"),
            await _request(h, p, "POST", "/v1/topk",
                           {"ids": [1], "k": 4, "deadline_ms": 1e-4}),
            await _request(h, p, "POST", "/v1/topk",
                           {"ids": [1], "k": 4}),  # still serving
        ]
        return rows

    rows, _ = _run(engine, go)
    (parse, bad_id, bad_dl, no_route, bad_method, expired, ok) = rows
    assert parse[0] == 400 and parse[1]["error"]["kind"] == "parse"
    assert bad_id[0] == 400 and bad_id[1]["error"]["kind"] == "validation"
    assert bad_dl[0] == 400 and bad_dl[1]["error"]["kind"] == "validation"
    assert no_route[0] == 404
    assert bad_method[0] == 405
    assert expired[0] == 504
    assert expired[1]["error"]["kind"] == "deadline_exceeded"
    assert ok[0] == 200 and "neighbors" in ok[1]


def test_deadline_expires_queued_in_collator_socket_path(engine):
    """Satellite contract, through the socket: a request whose deadline
    expires while queued in the collator is never dispatched and
    answers deadline_exceeded (HTTP 504) — queue time counts against
    the budget because t_enq is the socket-in stamp."""
    reg = telem.default_registry()

    async def go(door, bat):
        base = reg.mark()
        status, r = await _request(
            door.host, door.port, "POST", "/v1/topk",
            {"ids": [7], "k": 4, "deadline_ms": 30.0})
        return base, status, r

    (base, status, r), _ = _run(engine, go, max_wait_us=500_000)
    assert status == 504
    assert r["error"]["kind"] == "deadline_exceeded"
    assert "queued in the collator" in r["error"]["message"]
    delta = reg.snapshot(baseline=base)
    assert delta.get("serve/deadline_exceeded") == 1
    assert delta.get("serve/slots", 0) == 0  # never dispatched
    # failed requests observe no latency histograms
    assert "hist/serve/e2e_ms" not in delta


def test_deadline_expires_mid_flight_still_caches_socket_path(engine):
    """Satellite contract, through the socket: a request that expires
    MID-FLIGHT (injected dispatch latency) answers 504 — but its rows
    stay cached, so the same ids answer 200 from cache right after."""
    reg = telem.default_registry()
    faults.install([faults.FaultSpec(site="serve.dispatch",
                                     kind="latency", ms=150.0,
                                     times=1)])

    async def go(door, bat):
        h, p = door.host, door.port
        base = reg.mark()
        late = await _request(h, p, "POST", "/v1/topk",
                              {"ids": [5, 6], "k": 4,
                               "deadline_ms": 60.0})
        mid = reg.snapshot(baseline=base)
        base2 = reg.mark()
        hot = await _request(h, p, "POST", "/v1/topk",
                             {"ids": [5, 6], "k": 4,
                              "deadline_ms": 60.0})
        return late, mid, hot, reg.snapshot(baseline=base2)

    (late, mid, hot, delta2), _ = _run(engine, go, max_wait_us=1_000,
                                       cache_size=1024)
    assert late[0] == 504
    assert late[1]["error"]["kind"] == "deadline_exceeded"
    assert mid.get("serve/slots") == 8  # it DID dispatch (too late)
    assert hot[0] == 200 and "neighbors" in hot[1]
    assert delta2.get("serve/cache_hit") == 2  # served from cache
    assert delta2.get("serve/slots", 0) == 0


def test_sustained_overload_sheds_http_429(engine):
    """More concurrent requests than queue_max: the excess answers
    HTTP 429 / typed overloaded — never unbounded queueing — and every
    request gets exactly one response."""
    async def go(door, bat):
        h, p = door.host, door.port
        return await asyncio.gather(
            *[_request(h, p, "POST", "/v1/topk", {"ids": [i], "k": 4})
              for i in range(10)])

    rows, door = _run(engine, go, queue_max=2, ladder_down_after=100,
                      max_wait_us=5_000)
    assert len(rows) == 10  # one response per request, exactly
    ok = [r for s, r in rows if s == 200]
    shed = [(s, r) for s, r in rows if s == 429]
    assert len(ok) + len(shed) == 10
    assert ok and shed
    assert all(r["error"]["kind"] == "overloaded" for _, r in shed)


def test_keep_alive_connection_serves_sequentially(engine):
    """HTTP/1.1 keep-alive: several requests down one connection each
    get one response; recompiles stay FLAT across same-bucket requests
    (the compile-once-per-bucket contract through the socket path)."""
    reg = telem.default_registry()

    async def go(door, bat):
        h, p = door.host, door.port
        # warm the bucket once (first (8,4) compile may land here)
        await _request(h, p, "POST", "/v1/topk", {"ids": [0], "k": 4})
        c0 = reg.get("jax/recompiles")
        s, r, rw = await _request(h, p, "POST", "/v1/topk",
                                  {"ids": [1], "k": 4}, keep_alive=True)
        assert s == 200
        for i in (2, 3, 4):
            s, r, rw = await _request(h, p, "POST", "/v1/topk",
                                      {"ids": [i], "k": 4},
                                      keep_alive=True, rw=rw)
            assert s == 200 and len(r["neighbors"]) == 1
        rw[1].close()
        return reg.get("jax/recompiles") - c0

    steady_recompiles, door = _run(engine, go)
    assert steady_recompiles == 0
    assert door.served >= 5


def test_drain_answers_inflight_and_refuses_new(engine):
    """Drain: the in-flight request is answered, the listener refuses
    new connections, an IDLE keep-alive connection cannot block the
    drain, and healthz reports not-ok while draining."""
    faults.install([faults.FaultSpec(site="serve.dispatch",
                                     kind="latency", ms=120.0,
                                     times=1)])

    async def go_outer():
        door, bat = _door(engine, max_wait_us=1_000)
        await door.start()
        h, p = door.host, door.port
        # an idle keep-alive connection parks in the read/drain race
        _s, _r, idle_rw = await _request(h, p, "POST", "/v1/topk",
                                         {"ids": [0], "k": 4},
                                         keep_alive=True)
        # in-flight slow request, then drain while it runs
        inflight = asyncio.ensure_future(
            _request(h, p, "POST", "/v1/topk", {"ids": [9], "k": 4}))
        await asyncio.sleep(0.03)  # let it reach the dispatch
        t0 = time.perf_counter()
        await door.drain()
        drain_s = time.perf_counter() - t0
        status, r = await inflight
        refused = False
        try:
            await asyncio.open_connection(h, p)
        except OSError:
            refused = True
        idle_rw[1].close()
        return status, r, refused, drain_s, door

    status, r, refused, drain_s, door = asyncio.run(go_outer())
    assert status == 200 and "neighbors" in r  # in-flight answered
    assert refused  # listener closed: new connections refused
    assert drain_s < 10.0  # the idle keep-alive did not block drain
    assert door.draining


def test_draining_healthz_and_stats_report_it(engine):
    async def go_outer():
        door, bat = _door(engine, max_wait_us=1_000)
        await door.start()
        # drain with no traffic, then probe state objects directly (the
        # listener is closed, so HTTP probes can't reach it — the
        # stats/health payloads are what a load balancer saw LAST)
        await door.drain()
        return door

    door = asyncio.run(go_outer())
    assert door.draining
    stats = door._stats()
    assert stats["server"]["draining"] is True


def test_oversized_and_malformed_protocol_lines(engine):
    async def go(door, bat):
        h, p = door.host, door.port
        # malformed request line: answered 400 + close, server survives
        reader, writer = await asyncio.open_connection(h, p)
        writer.write(b"garbage\r\n\r\n")
        await writer.drain()
        first = await reader.readline()
        writer.close()
        # bad Content-Length
        reader, writer = await asyncio.open_connection(h, p)
        writer.write(b"POST /v1/topk HTTP/1.1\r\n"
                     b"Content-Length: banana\r\n\r\n")
        await writer.drain()
        second = await reader.readline()
        writer.close()
        # oversized body: 413, typed validation, BEFORE reading it
        reader, writer = await asyncio.open_connection(h, p)
        writer.write(b"POST /v1/topk HTTP/1.1\r\n"
                     b"Content-Length: 999999999\r\n\r\n")
        await writer.drain()
        third = await reader.readline()
        writer.close()
        ok = await _request(h, p, "POST", "/v1/topk",
                            {"ids": [1], "k": 4})
        return first, second, third, ok

    (first, second, third, ok), _ = _run(engine, go)
    assert b"400" in first
    assert b"400" in second
    assert b"413" in third
    assert ok[0] == 200


def test_cli_serve_http_bind_failure_is_clean_usage_error(engine,
                                                          tmp_path):
    """A port already in use answers a clean SystemExit, not an asyncio
    traceback (the CLI's usage-error contract)."""
    import socket

    from hyperspace_tpu.cli import serve as S

    # hold a port so the bind fails deterministically
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    try:
        cfg = S.ServeConfig(artifact="unused", port=port)

        def fake_build(_cfg):
            bat = RequestBatcher(engine, min_bucket=8, max_bucket=64)
            return engine, bat

        orig = S._build
        S._build = fake_build
        try:
            with pytest.raises(SystemExit, match="cannot bind"):
                S.run_serve_http(cfg)
        finally:
            S._build = orig
    finally:
        sock.close()
