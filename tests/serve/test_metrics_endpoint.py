"""Serving /metrics and /healthz endpoints (ISSUE 5 satellites).

``GET /metrics`` must return valid Prometheus text exposition carrying
series from every subsystem wired to the registry (compile cache,
overlap dispatch, checkpointing, serving); ``GET /healthz`` follows the
:class:`alpa_tpu.fault.RecoveryManager` state machine — 200 while
HEALTHY/SUSPECT/RECOVERING, 503 once DEGRADED — and falls back to the
controller health report when no recovery manager is attached.
"""
import json
import urllib.error
import urllib.request

import pytest

from alpa_tpu.fault import MeshHealth, RecoveryManager, RetryPolicy
from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve import Generator, run_controller

pytestmark = pytest.mark.fault


def _tiny_generator(batch_size=1):
    cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=32,
                    vocab_size=64)
    model, params = init_gpt_real(cfg, batch_size)
    return Generator(model, params, cfg, batch_size)


def _get(base, path):
    """(status, body bytes) — 4xx/5xx don't raise."""
    try:
        with urllib.request.urlopen(base + path) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


class TestMetricsEndpoint:

    def test_metrics_exposition(self):
        server = run_controller(port=0)
        try:
            server.controller.register_model("tiny", _tiny_generator())
            base = f"http://127.0.0.1:{server.port}"
            # drive one request through so serving series carry traffic
            req = urllib.request.Request(
                base + "/completions",
                data=json.dumps({"model": "tiny", "prompt_ids": [1, 2],
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as r:
                assert r.status == 200

            status, body, headers = _get(base, "/metrics")
            assert status == 200
            assert headers["Content-Type"].startswith("text/plain")
            text = body.decode()

            # basic exposition validity: every non-comment line is
            # "name{labels} value"
            for line in text.strip().splitlines():
                if line.startswith("#"):
                    assert line.startswith(("# HELP ", "# TYPE "))
                    continue
                name_part, _, value = line.rpartition(" ")
                assert name_part and value
                if value != "+Inf":
                    float(value)

            # one series per instrumented subsystem
            assert "alpa_compile_cache_memory_entries" in text
            assert "alpa_overlap_steps_total" in text
            assert "alpa_checkpoint_stat_total" in text
            assert "alpa_serving_requests_total" in text
            assert 'alpa_serving_requests_total{outcome="ok"}' in text
            assert "alpa_serving_batch_size_bucket" in text
            assert "alpa_serving_queue_depth" in text
            # set when the engine's decode was traced: heads of 8 channels
            # are narrower than the lanes
            assert ('alpa_cache_row_write_view{view="positions_minor",'
                    'heads="4",head_dim="8"}') in text
            # and which core its attention over the written cache took: a
            # cache of 32 positions is in no whole key block
            assert ('alpa_cached_attention_core{core="reference",'
                    'heads="4",head_dim="8",queries="1"}') in text
            assert "alpa_serving_decode_positions_read_total" in text
            assert "alpa_fault_health_state" in text
            assert "alpa_watchdog_last_ok_timestamp" in text
        finally:
            server.shutdown()


class TestHealthzEndpoint:

    def test_healthz_without_recovery_follows_health_report(self):
        server = run_controller(port=0)
        try:
            base = f"http://127.0.0.1:{server.port}"
            status, body, _ = _get(base, "/healthz")
            assert status == 200
            assert json.loads(body)["status"] == "ok"
            server.controller.set_health("shedding", "test")
            status, _, _ = _get(base, "/healthz")
            assert status == 503
        finally:
            server.shutdown()

    def test_healthz_flips_503_when_recovery_degrades(self):
        """THE acceptance wire: the watchdog's recovery manager entering
        DEGRADED (via failing probes) flips /healthz from 200 to 503;
        recovery flips it back."""
        server = run_controller(port=0)
        try:
            base = f"http://127.0.0.1:{server.port}"
            alive = {"ok": True}
            rm = RecoveryManager(
                [object()],
                retry_policy=RetryPolicy(max_attempts=2,
                                         base_delay=0.001, jitter=0.0),
                probe=lambda mesh: alive["ok"])
            server.controller.attach_recovery(rm)

            status, body, _ = _get(base, "/healthz")
            assert status == 200
            assert json.loads(body)["status"] == "healthy"

            alive["ok"] = False
            assert rm.tick() is MeshHealth.DEGRADED
            status, body, _ = _get(base, "/healthz")
            assert status == 503
            assert json.loads(body)["status"] == "degraded"

            alive["ok"] = True
            assert rm.tick() is MeshHealth.HEALTHY
            status, _, _ = _get(base, "/healthz")
            assert status == 200
        finally:
            server.shutdown()

    def test_recovery_state_mirrored_to_registry(self):
        from alpa_tpu.telemetry import metrics as tmetrics
        alive = {"ok": True}
        rm = RecoveryManager(
            [object()],
            retry_policy=RetryPolicy(max_attempts=2, base_delay=0.001,
                                     jitter=0.0),
            probe=lambda mesh: alive["ok"])
        alive["ok"] = False
        assert rm.tick() is MeshHealth.DEGRADED
        reg = tmetrics.get_registry()
        assert reg.get("alpa_fault_health_state").value == 3
        alive["ok"] = True
        assert rm.tick() is MeshHealth.HEALTHY
        assert reg.get("alpa_fault_health_state").value == 0
        snap = reg.snapshot()
        assert snap.get(
            'alpa_fault_state_transitions_total{to="degraded"}', 0) >= 1
