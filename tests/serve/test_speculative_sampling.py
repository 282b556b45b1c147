"""Sampled speculative decoding is exact IN DISTRIBUTION (VERDICT r4
next #6): rejection-sampling acceptance (Leviathan et al.) makes every
emitted token target-distributed regardless of the draft.

Two layers of proof:
  1. the acceptance math itself (``speculative_accept``) — the marginal
     of the first emitted token over many synthetic rounds equals the
     target row p_0 exactly (TV distance -> 0), for adversarial q;
  2. end-to-end on a tiny model — the empirical joint of the first two
     sampled tokens from ``generate_speculative(do_sample=True)``
     matches the exact joint computed from the target's own warped
     logits (the same check a plain-sampling run would pass).
"""
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve.generation import (GenerationConfig, Generator,
                                       _sample_from_probs, _warp_probs_np,
                                       speculative_accept)


def _tv(p, q):
    return 0.5 * float(np.abs(np.asarray(p) - np.asarray(q)).sum())


class TestAcceptanceMath:

    @pytest.mark.parametrize("case", ["random", "disjointish", "equal"])
    def test_first_token_marginal_is_exactly_target(self, case):
        """Simulate many speculative rounds against fixed q/p tensors;
        the first emitted token's empirical distribution must converge
        to p_0 — the defining property of speculative sampling."""
        rng = np.random.RandomState(0)
        V, k, N = 8, 3, 200_000
        q = rng.dirichlet(np.ones(V), size=k)
        p = rng.dirichlet(np.ones(V), size=k + 1)
        if case == "disjointish":
            # draft mass concentrated where the target is thin
            q = rng.dirichlet(np.full(V, 0.2), size=k)
        elif case == "equal":
            p[:k] = q
        counts = np.zeros(V)
        for _ in range(N):
            props = [_sample_from_probs(q[i], rng.uniform())
                     for i in range(k)]
            a, extra = speculative_accept(props, q, p, rng.uniform(size=k),
                                          rng.uniform())
            first = props[0] if a >= 1 else extra
            counts[first] += 1
        assert _tv(counts / N, p[0]) < 0.01, (case, counts / N, p[0])

    def test_equal_distributions_accept_everything(self):
        rng = np.random.RandomState(1)
        V, k = 16, 4
        q = rng.dirichlet(np.ones(V), size=k)
        p = np.concatenate([q, rng.dirichlet(np.ones(V), size=1)])
        for _ in range(500):
            props = [_sample_from_probs(q[i], rng.uniform())
                     for i in range(k)]
            a, _extra = speculative_accept(props, q, p,
                                           rng.uniform(size=k),
                                           rng.uniform())
            assert a == k

    def test_warp_matches_sample_logits_support(self):
        """_warp_probs_np's top-k semantics match _sample_logits: mass
        only on the top-k (ties at the k-th value included)."""
        logits = np.array([1.0, 3.0, 3.0, 0.0, 2.0])
        p = _warp_probs_np(logits, GenerationConfig(do_sample=True,
                                                    top_k=2))
        assert p[3] == 0.0 and p[0] == 0.0
        assert p[1] > 0 and p[2] > 0 and p[4] == 0.0
        np.testing.assert_allclose(p.sum(), 1.0)

    def test_top_k_mask_constant_unified(self):
        """The device sampler and the host warper share ONE mask
        constant, and it is -inf: a finite sentinel (the old -1e9)
        leaves masked tokens with tiny-but-nonzero device probability
        while the host assigns exactly zero — speculative acceptance
        p/q is only exact when both agree on the support."""
        import jax
        import jax.numpy as jnp

        from alpa_tpu.serve import generation

        assert generation.TOP_K_MASK == float("-inf")
        logits = np.array([1.0, 3.0, 3.0, 0.0, 2.0], np.float32)
        cfg = GenerationConfig(do_sample=True, top_k=2)
        # device-path probabilities under exactly _sample_logits' warp
        x = jnp.asarray(logits)
        kth = jax.lax.top_k(x, cfg.top_k)[0][..., -1:]
        dev_p = np.asarray(jax.nn.softmax(
            jnp.where(x < kth, generation.TOP_K_MASK, x)), np.float64)
        host_p = _warp_probs_np(logits, cfg)
        # identical support: zero exactly where the other is zero
        np.testing.assert_array_equal(dev_p == 0.0, host_p == 0.0)
        np.testing.assert_allclose(dev_p, host_p, atol=1e-6)


class TestEndToEndSampled:

    def test_sampled_joint_matches_target_chain(self):
        """Empirical (t0, t1) joint over many seeded speculative runs ==
        the exact joint from the target's warped logits."""
        import jax.numpy as jnp

        cfg_t = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                          seq_len=32, vocab_size=32)
        model_t, params_t = init_gpt_real(cfg_t, 1)
        target = Generator(model_t, params_t, cfg_t, prompt_buckets=[8])
        cfg_d = GPTConfig(hidden_size=16, num_layers=1, num_heads=2,
                          seq_len=32, vocab_size=32)
        model_d, params_d = init_gpt_real(cfg_d, 1)
        draft = Generator(model_d, params_d, cfg_d, prompt_buckets=[8])

        prompt = np.array([5, 3, 1], np.int32)
        gcfg = GenerationConfig(max_new_tokens=2, do_sample=True,
                                temperature=1.5, top_k=3)

        # exact joint from the target itself: p(t0) from the prefill
        # logits; p(t1 | t0) from one cached decode per t0 in support
        logits0, caches0 = target._spec_prefill(target, prompt)
        p0 = _warp_probs_np(np.asarray(logits0)[0], gcfg)
        support0 = np.nonzero(p0)[0]
        joint = {}
        for t0 in support0:
            # _decode takes the K and V arrays it is given: every branch
            # from the prefilled state decodes a copy of them
            branch = [(jnp.copy(k), jnp.copy(v), i) for k, v, i in caches0]
            l1, _, _ = target._decode(
                target.params, jnp.asarray([[int(t0)]], jnp.int32),
                caches0[0][2], branch)
            p1 = _warp_probs_np(np.asarray(l1)[0], gcfg)
            for t1 in np.nonzero(p1)[0]:
                joint[(int(t0), int(t1))] = float(p0[t0] * p1[t1])

        N = 1500
        counts = {}
        for seed in range(N):
            out, _stats = target.generate_speculative(
                draft, prompt, gcfg, num_draft=2, seed=seed)
            t0, t1 = int(out[len(prompt)]), int(out[len(prompt) + 1])
            counts[(t0, t1)] = counts.get((t0, t1), 0) + 1

        assert set(counts) <= set(joint), (
            "sampled a pair outside the target's warped support",
            sorted(set(counts) - set(joint)))
        keys = sorted(joint)
        emp = np.array([counts.get(kk, 0) / N for kk in keys])
        exact = np.array([joint[kk] for kk in keys])
        # TV tolerance ~3 sigma for N=1500 over <=9 support pairs
        assert _tv(emp, exact) < 0.06, (dict(zip(keys, emp)), joint)

    def test_greedy_zero_temperature_limit_unchanged(self):
        """do_sample with the greedy path still matches plain greedy
        (regression guard: the sampled path must not perturb greedy)."""
        cfg_t = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                          seq_len=32, vocab_size=32)
        model_t, params_t = init_gpt_real(cfg_t, 1)
        target = Generator(model_t, params_t, cfg_t, prompt_buckets=[8])
        prompt = np.array([7, 2, 4], np.int32)
        want = target.generate(prompt[None],
                               GenerationConfig(max_new_tokens=8))
        got, _ = target.generate_speculative(
            target, prompt, GenerationConfig(max_new_tokens=8),
            num_draft=3)
        np.testing.assert_array_equal(got, np.asarray(want)[0])


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
