"""Disaggregated prefill/decode serving (serve/disagg.py, ISSUE 18).

Tier-1 pinned invariants:
* the disaggregated decode stream is BIT-EXACT (np.array_equal) vs the
  monolithic ContinuousBatchingEngine on the miss, full-hit, and
  shared-prefix paths;
* ``disagg_mode=off`` routes byte-identically to the monolithic path;
* no handoff is ever dropped: a decode-replica death (at ingest or
  mid-stream) re-ingests the retained artifact on a survivor with
  bitwise-identical output, and a corrupt artifact (flipped block hash)
  is rejected + re-fetched — never silently decoded.
"""
import threading
import time

import numpy as np
import pytest

from alpa_tpu import fault
from alpa_tpu.global_env import global_config
from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve import disagg
from alpa_tpu.serve.controller import Controller
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.serve.router import LocalReplicaHandle, Router

BS = 8

PROMPT = np.array([5, 9, 3, 7, 1, 2, 8, 4, 6, 11, 13, 2], np.int32)
GCFG = GenerationConfig(max_new_tokens=6, temperature=0.0)
REQ = {"model": "m", "prompt_ids": PROMPT.tolist(),
       "max_new_tokens": 6, "temperature": 0.0}


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                    seq_len=64, vocab_size=64)
    model, params = init_gpt_real(cfg, 1)
    return model, params, cfg


def _gen(tiny):
    model, params, cfg = tiny
    return Generator(model, params, cfg, prefill_chunk=BS)


def _paged_engine(tiny, block_size=BS):
    gen = _gen(tiny)
    pool = KVBlockPool.for_generator(gen, max_batch=2,
                                     block_size=block_size)
    return ContinuousBatchingEngine(gen, max_batch=2, kv_pool=pool)


@pytest.fixture
def paged(tiny):
    global_config.kv_paged, prev_p = True, global_config.kv_paged
    global_config.kv_prefix_reuse, prev_r = \
        True, global_config.kv_prefix_reuse
    yield
    global_config.kv_paged = prev_p
    global_config.kv_prefix_reuse = prev_r


class TestArtifact:
    """Wire-format + content-hash mechanics (no engines)."""

    def _artifact(self, tiny, codec="off"):
        gen = _gen(tiny)
        pe = disagg.PrefillEngine(gen, model="m", codec=codec)
        try:
            return pe.prefill(PROMPT, GCFG)
        finally:
            pe.shutdown()

    def test_wire_roundtrip_identical(self, tiny):
        art = self._artifact(tiny)
        back = disagg.KVHandoffArtifact.from_wire(art.to_wire())
        assert back.request_id == art.request_id
        assert np.array_equal(back.prompt, art.prompt)
        assert np.array_equal(back.last_logits, art.last_logits)
        for lay_a, lay_b in zip(art.layers, back.layers):
            for key in lay_a:
                assert np.array_equal(lay_a[key], lay_b[key])
        assert back.block_hashes == art.block_hashes
        # deterministic wire: re-fetching serializes identical bytes
        assert art.to_wire() == back.to_wire()

    def test_flipped_block_hash_rejected(self, tiny):
        wire = self._artifact(tiny).to_wire()
        wire["block_hashes"][0] = "0" * 64
        with pytest.raises(disagg.ArtifactCorruptError):
            disagg.KVHandoffArtifact.from_wire(wire)

    def test_corrupt_payload_rejected(self, tiny):
        wire = self._artifact(tiny).to_wire()
        data = wire["layers"][0]["k"]["data"]
        wire["layers"][0]["k"]["data"] = \
            ("A" if data[0] != "A" else "B") + data[1:]
        with pytest.raises(disagg.ArtifactCorruptError):
            disagg.KVHandoffArtifact.from_wire(wire)

    def test_malformed_wire_rejected(self, tiny):
        wire = self._artifact(tiny).to_wire()
        del wire["layers"]
        with pytest.raises(disagg.ArtifactCorruptError):
            disagg.KVHandoffArtifact.from_wire(wire)

    def test_codec_int8_roundtrip_within_bound(self, tiny):
        from alpa_tpu.pipeline_parallel import reshard_codec
        raw = self._artifact(tiny, codec="off")
        art = self._artifact(tiny, codec="int8")
        assert "k_q" in art.layers[0]
        back = disagg.KVHandoffArtifact.from_wire(art.to_wire())
        for l, lay in enumerate(raw.layers):
            tail = lay["k"].shape[2:]
            kq, _vq = back.dense_rows(l, tail)
            kraw = lay["k"].reshape((-1,) + tail)
            scale = np.abs(kraw).max() or 1.0
            err = np.abs(kq - kraw).max() / scale
            assert err <= reshard_codec.ERROR_BOUND["int8"] * 4
        # quantized payload is hashed over the wire form: verify holds
        back.verify()


class TestBitExact:
    """Pinned: disagg decode == monolithic engine, all reuse paths."""

    def test_miss_fullhit_shared_prefix(self, tiny):
        mono = _paged_engine(tiny)
        dec = _paged_engine(tiny)
        gen = _gen(tiny)
        # block_size 4 so the 8-token shared prefix spans full blocks
        # and the LATER prefills really take the gather + chunked-suffix
        # hit path (block_size 16 would round every match down to zero)
        pool = KVBlockPool.for_generator(gen, block_size=4,
                                         prefix_reuse=True)
        pe = disagg.PrefillEngine(gen, model="m", kv_pool=pool,
                                  prompt_bucket=gen.prompt_buckets[-1])
        try:
            p2 = np.concatenate(
                [PROMPT[:8], np.array([21, 22, 23, 24], np.int32)])
            for label, p in (("miss", PROMPT),
                             ("shared-prefix", p2),
                             ("full-hit", PROMPT)):
                ref = mono.submit(p, GCFG)
                art = disagg.KVHandoffArtifact.from_wire(
                    pe.prefill(p, GCFG).to_wire())
                out = disagg.ingest(dec, art)
                assert np.array_equal(np.asarray(ref),
                                      np.asarray(out)), label
        finally:
            pe.shutdown()
            mono.shutdown()
            dec.shutdown()

    @pytest.mark.parametrize("n", [12, 40])
    def test_a_miss_pads_as_the_monolithic_admission_does(self, tiny, n):
        """Both engines pad a miss to the smallest step of one admission
        ladder (32 and the cap, 64, here) through one function: a prompt
        under the step and one over it are handed off bit for bit."""
        model, params, cfg = tiny
        gen = Generator(model, params, cfg)
        cap = gen.prompt_buckets[-1]
        assert gen.admission_ladder(cap) == [32, 64]
        prompt = np.random.default_rng(n).integers(
            1, 60, n).astype(np.int32)
        engines = [ContinuousBatchingEngine(gen, max_batch=2,
                                            prompt_bucket=cap)]
        try:
            ref = engines[0].submit(prompt, GCFG)
            # the ladder's two programs are compiled; the engines made
            # from here on run them, and trace none
            assert gen.prefill_traces == 2
            dec = ContinuousBatchingEngine(gen, max_batch=2,
                                           prompt_bucket=cap)
            pe = disagg.PrefillEngine(gen, model="m", prompt_bucket=cap)
            engines += [dec, pe]
            assert pe._ladder == engines[0]._ladder == [32, 64]
            art = disagg.KVHandoffArtifact.from_wire(
                pe.prefill(prompt, GCFG).to_wire())
            assert np.array_equal(np.asarray(ref),
                                  np.asarray(disagg.ingest(dec, art)))
            assert gen.prefill_traces == 2
        finally:
            for engine in engines:
                engine.shutdown()

    def test_prefill_side_prefix_hits_accumulate(self, tiny):
        gen = _gen(tiny)
        pool = KVBlockPool.for_generator(gen, block_size=4,
                                         prefix_reuse=True)
        pe = disagg.PrefillEngine(gen, model="m", kv_pool=pool)
        try:
            pe.prefill(PROMPT, GCFG)
            before = pe.pool.stats()["prefix_hits"]
            pe.prefill(PROMPT, GCFG)
            assert pe.pool.stats()["prefix_hits"] == before + 1
        finally:
            pe.shutdown()

    def test_decode_side_registers_prefix_chain(self, tiny):
        """Ingest must register the prompt chain in the DECODE pool so
        later monolithic submits on that replica still hit."""
        dec = _paged_engine(tiny)
        gen = _gen(tiny)
        pe = disagg.PrefillEngine(gen, model="m")
        try:
            disagg.ingest(dec, pe.prefill(PROMPT, GCFG))
            hits_before = dec._pool.stats()["prefix_hits"]
            dec.submit(PROMPT, GCFG)
            assert dec._pool.stats()["prefix_hits"] > hits_before
        finally:
            pe.shutdown()
            dec.shutdown()


def _fleet(tiny, n_decode=2, **router_kw):
    """1 prefill + n decode controllers behind a phase-aware router."""
    cp = Controller()
    cp.register_model("m", _gen(tiny))
    r = Router(disagg_mode="auto", **router_kw)
    r.add_replica("p0", LocalReplicaHandle(cp), phase="prefill")
    decs = []
    for i in range(n_decode):
        cd = Controller()
        cd.register_model("m", _gen(tiny))
        r.add_replica(f"d{i}", LocalReplicaHandle(cd), phase="decode")
        decs.append(cd)
    return r, cp, decs


class TestRouterDisagg:

    def test_router_disagg_matches_monolithic(self, tiny, paged):
        c0 = Controller()
        c0.register_model("m", _gen(tiny))
        r0 = Router(disagg_mode="off")
        r0.add_replica("solo", LocalReplicaHandle(c0))
        ref = r0.submit(dict(REQ))

        r, _cp, _ = _fleet(tiny)
        assert r.snapshot()["disagg"]["active"]
        out = r.submit(dict(REQ))
        assert out == ref
        assert r.disagg_handoffs == 1

    def test_mode_off_is_monolithic_path(self, tiny, paged):
        """disagg_mode=off never touches the disagg path even with
        phased replicas present: handoff counters stay zero and phased
        pools are simply ignored for placement filtering."""
        c0 = Controller()
        c0.register_model("m", _gen(tiny))
        r = Router(disagg_mode="off")
        r.add_replica("a", LocalReplicaHandle(c0), phase="prefill")
        assert not r._disagg_active()
        out = r.submit(dict(REQ))
        assert out["output_ids"][0][:len(PROMPT)] == PROMPT.tolist()
        assert r.disagg_handoffs == 0
        assert r.snapshot()["disagg"]["active"] is False

    def test_auto_needs_both_pools(self, tiny, paged):
        c0 = Controller()
        c0.register_model("m", _gen(tiny))
        r = Router(disagg_mode="auto")
        r.add_replica("p0", LocalReplicaHandle(c0), phase="prefill")
        assert not r._disagg_active()  # no decode pool yet

    def test_ack_releases_retained_artifact(self, tiny, paged):
        r, cp, _ = _fleet(tiny)
        r.submit(dict(REQ))
        pe = cp._models["m"][0]._prefill_engine
        with pe._cv:
            assert len(pe._retained) == 0, \
                "clean stream end must ack the retained artifact"

    def test_backpressure_throttles_prefill_admission(self, tiny,
                                                      paged):
        r, _cp, _ = _fleet(tiny, disagg_backpressure_depth=1)
        # inflate the decode pool's apparent backlog
        for name in ("d0", "d1"):
            r._replicas[name].inflight = 5
        with pytest.raises(fault.ServiceDegradedError,
                           match="backpressure"):
            r.submit(dict(REQ))
        assert r.disagg_backpressure_sheds == 1
        # backlog clears -> admission resumes
        for name in ("d0", "d1"):
            r._replicas[name].inflight = 0
        assert r.submit(dict(REQ))["output_ids"]


class TestFailover:
    """No handoff is ever dropped (ISSUE 18 satellite 4)."""

    def test_decode_death_at_ingest_reingests_bitexact(self, tiny,
                                                       paged):
        ref_r, _cp0, _ = _fleet(tiny)
        ref = ref_r.submit(dict(REQ))

        r, _cp, _decs = _fleet(tiny)
        st = r._replicas["d0"]
        real = st.handle

        class DeadIngest:
            def __getattr__(self, k):
                if k == "ingest":
                    def boom(wire):
                        raise ConnectionError("decode replica down")
                    return boom
                return getattr(real, k)
        st.handle = DeadIngest()
        out = r.submit(dict(REQ))
        assert out == ref, "re-ingested output must be bit-identical"
        assert r.disagg_reingests == 1
        assert st.fails == 1, "dead replica is health-counted"

    def test_decode_death_mid_stream_reingests_bitexact(self, tiny,
                                                        paged):
        ref_r, _cp0, _ = _fleet(tiny)
        ref = ref_r.submit(dict(REQ))["output_ids"][0]

        r, _cp, _decs = _fleet(tiny)
        stream = r.submit_stream(dict(REQ, stream=True))
        toks = [next(stream), next(stream)]

        class DyingIter:
            def __next__(self):
                raise ConnectionError("decode died mid-stream")

            def __iter__(self):
                return self

            def close(self):
                pass
        died = stream._dst.name
        stream._inner = DyingIter()
        toks.extend(stream)
        assert PROMPT.tolist() + toks == ref
        assert r.disagg_reingests == 1
        assert stream._dst.name != died, "stream moved to a survivor"

    def test_corrupt_artifact_refetched_never_decoded(self, tiny,
                                                      paged):
        ref_r, _cp0, _ = _fleet(tiny)
        ref = ref_r.submit(dict(REQ))

        r, _cp, _decs = _fleet(tiny, n_decode=1)
        st = r._replicas["d0"]
        real = st.handle
        flips = {"n": 0}

        class CorruptingWire:
            """Flip a block hash on the first wire copy only — models
            one-shot transport corruption."""

            def __getattr__(self, k):
                if k == "ingest":
                    def ingest(wire):
                        if flips["n"] == 0:
                            flips["n"] += 1
                            wire = dict(wire,
                                        block_hashes=["f" * 64] +
                                        wire["block_hashes"][1:])
                        return real.ingest(wire)
                    return ingest
                return getattr(real, k)
        st.handle = CorruptingWire()
        out = r.submit(dict(REQ))
        assert out == ref, "re-fetched artifact must decode bit-exact"
        assert flips["n"] == 1
        assert r.disagg_reingests == 1

    def test_sampled_stream_propagates_decode_death(self, tiny, paged):
        """do_sample streams cannot replay deterministically — the
        failure surfaces instead of silently diverging."""
        r, _cp, _decs = _fleet(tiny)
        req = dict(REQ, stream=True, do_sample=True, temperature=0.7)
        stream = r.submit_stream(req)
        next(stream)

        class DyingIter:
            def __next__(self):
                raise ConnectionError("boom")

            def __iter__(self):
                return self

            def close(self):
                pass
        stream._inner = DyingIter()
        with pytest.raises(ConnectionError):
            list(stream)


class TestFairness:
    """ISSUE 18 satellite 3: a tenant's WFQ weight holds on the
    disaggregated prefill pool — a flooding tenant cannot starve
    another tenant's admission (and therefore its decode SLO)."""

    def test_weighted_tenant_jumps_flooded_queue(self, tiny):
        from alpa_tpu.serve.scheduler import WeightedFairQueue
        gen = _gen(tiny)
        pe = disagg.PrefillEngine(
            gen, model="m",
            scheduler=WeightedFairQueue({"paid": 8, "flood": 1}))
        order = []
        lock = threading.Lock()
        gate = threading.Event()
        in_first = threading.Event()
        orig = pe._prefill_one

        def gated(item):
            # park the worker inside the FIRST request so the flood and
            # the paid request pile up behind it deterministically
            if not in_first.is_set():
                in_first.set()
                gate.wait(timeout=60)
            return orig(item)
        pe._prefill_one = gated

        def one(tenant, i):
            pe.prefill(PROMPT, GCFG, queue=tenant,
                       request_id=f"{tenant}-{i}")
            with lock:
                order.append(tenant)
        try:
            hold = threading.Thread(target=one, args=("flood", 99))
            hold.start()
            assert in_first.wait(timeout=60)
            threads = [threading.Thread(target=one, args=("flood", i))
                       for i in range(6)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while pe.queue_depth() < 6:
                assert time.monotonic() < deadline, pe.queue_depth()
                time.sleep(0.001)
            paid = threading.Thread(target=one, args=("paid", 0))
            paid.start()
            while pe.queue_depth() < 7:
                assert time.monotonic() < deadline, pe.queue_depth()
                time.sleep(0.001)
            gate.set()
            for t in [hold, paid] + threads:
                t.join(timeout=60)
        finally:
            gate.set()
            pe.shutdown()
        # the paid tenant (weight 8) must not sit behind the whole
        # flood: it completes within the first few slots
        assert "paid" in order
        assert order.index("paid") <= 2, order

    def test_queue_tag_rides_artifact_to_decode_pool(self, tiny):
        gen = _gen(tiny)
        pe = disagg.PrefillEngine(gen, model="m")
        try:
            art = pe.prefill(PROMPT, GCFG, queue="tenant-a")
        finally:
            pe.shutdown()
        wire = art.to_wire()
        assert wire["queue"] == "tenant-a"
        back = disagg.KVHandoffArtifact.from_wire(wire)
        assert back.queue == "tenant-a"
