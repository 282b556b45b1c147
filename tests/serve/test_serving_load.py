"""Serving under concurrent load (VERDICT r4 next #5): N simultaneous
HTTP clients — mixed SSE + non-streaming — against the controller +
engine must all succeed, overlap their work (no serialization through
the ThreadingHTTPServer or the engine lock), and keep streaming TTFT
bounded.  The harness is scripts/serving_load_bench.py.
"""
from scripts.serving_load_bench import run_load


def test_concurrent_mixed_load():
    stats = run_load(n_clients=8, n_requests=2, max_new_tokens=6)
    assert stats["errors"] == [], stats
    assert stats["ok"] == 16, stats
    # concurrency: total client-observed time must overlap heavily
    assert stats["sum_of_individual_s"] > 2 * stats["wall_s"], stats
    # streaming stays responsive while the batch path churns (loose
    # bound: CI boxes are noisy; steady-state p99 measures ~0.2s)
    assert stats["sse_ttft_p99_s"] < 5.0, stats
