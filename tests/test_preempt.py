"""Preemption-notice poller (runtime/preempt.py): the real trigger for
the serving drain path, against a local stand-in metadata server.

The GCE boundary is simulated (a stdlib HTTP server flipping
``instance/preempted`` from FALSE to TRUE); everything downstream —
watcher thread, fire-once semantics, ``engine.request_drain()``, the
serve loop's drain, snapshot persistence hooks — is the production
path, same discipline as the fault-injection plane.
"""

import http.server
import threading
import time

import jax
import numpy as np
import pytest

from akka_allreduce_tpu.runtime.preempt import PreemptionWatcher


class _MetaState:
    def __init__(self):
        self.preempted = False
        self.requests = 0


def _serve_metadata(state):
    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            state.requests += 1
            body = b"TRUE" if state.preempted else b"FALSE"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}/preempted"


class TestPollOnce:
    def test_reads_flag(self):
        state = _MetaState()
        srv, url = _serve_metadata(state)
        try:
            w = PreemptionWatcher(lambda: None, url=url)
            assert w.poll_once() is False
            state.preempted = True
            assert w.poll_once() is True
            assert w.errors == 0
        finally:
            srv.shutdown()

    def test_unreachable_reads_false(self):
        """No metadata server (every non-GCE box): polls read False
        and count errors — never raise, never fire."""
        w = PreemptionWatcher(lambda: None,
                              url="http://127.0.0.1:1/preempted",
                              timeout_s=0.2)
        assert w.poll_once() is False
        assert w.errors == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="interval_s"):
            PreemptionWatcher(lambda: None, interval_s=0.0)


class TestWatcherThread:
    def test_fires_once_then_stops(self):
        state = _MetaState()
        srv, url = _serve_metadata(state)
        fired = []
        try:
            with PreemptionWatcher(lambda: fired.append(1), url=url,
                                   interval_s=0.02) as w:
                time.sleep(0.1)
                assert not w.fired
                state.preempted = True
                deadline = time.monotonic() + 3.0
                while not w.fired and time.monotonic() < deadline:
                    time.sleep(0.02)
            assert w.fired
            assert fired == [1]  # exactly once; thread exits after
        finally:
            srv.shutdown()

    def test_drives_serving_drain(self):
        """End to end: the notice stops admission and drains in-flight
        requests as resumable snapshots — the PR 5 loose end closed
        with a REAL (simulated-endpoint) trigger instead of SIGTERM."""
        from akka_allreduce_tpu.models.transformer import (
            TransformerConfig,
            init_transformer,
        )
        from akka_allreduce_tpu.serving import (
            PagedEngineConfig,
            PagedServingEngine,
            Request,
            RequestScheduler,
            SchedulerConfig,
            serve_loop,
        )
        cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=32)
        params = init_transformer(jax.random.key(0), cfg)
        state = _MetaState()
        srv, url = _serve_metadata(state)
        try:
            engine = PagedServingEngine(
                params, cfg, PagedEngineConfig(num_slots=2, page_size=4))
            sched = RequestScheduler(SchedulerConfig(), num_slots=2)
            rng = np.random.default_rng(3)
            reqs = [Request(rid=i,
                            prompt=tuple(int(x) for x in rng.integers(
                                0, 61, size=4)),
                            max_new_tokens=24, submitted_at=0.0)
                    for i in range(48)]
            for r in reqs:
                sched.submit(r)

            # the notice is raised by PROGRESS, not by a timer: with the
            # programs already compiled (a warm persistent cache) six
            # requests finish in ~50 ms, before any fixed delay. 48
            # requests outlast the watcher's poll by a wide margin, and
            # whatever completes before the notice keeps its result.
            def raise_notice_once_decoding():
                while engine.decode_dispatches < 3:
                    time.sleep(0.001)
                state.preempted = True

            flip = threading.Thread(target=raise_notice_once_decoding,
                                    daemon=True)
            flip.start()
            with PreemptionWatcher(engine.request_drain, url=url,
                                   interval_s=0.03) as w:
                results = serve_loop(engine, sched, max_dispatches=5000)
            flip.join(timeout=10)
            assert w.fired
            assert engine.drained, "notice did not drain in-flight work"
            assert engine.pool.pages_in_use == 0
            # the snapshots restore with bitwise parity — the drain
            # contract the notice now triggers for real
            fresh = PagedServingEngine(
                params, cfg, PagedEngineConfig(num_slots=2, page_size=4))
            while engine.drained or sched.unfinished:
                for rr in engine.drained:
                    sched.bind(rr.req, fresh.restore(rr))
                results.update(serve_loop(fresh, sched,
                                          max_dispatches=5000))
                engine = fresh
            from akka_allreduce_tpu.models.generate import generate
            import jax.numpy as jnp
            for r in reqs:
                want = np.asarray(generate(
                    params, jnp.asarray(r.prompt, jnp.int32)[None], cfg,
                    steps=r.max_new_tokens))[0]
                np.testing.assert_array_equal(
                    np.asarray(results[r.rid][0], np.int32), want)
        finally:
            srv.shutdown()
