"""An engine over a static prefix (``Generator.cache_prefix``): every
admission prefills only its suffix over a copy of the shared prefix K/V.
"""
import threading

import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator

CFG = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=32,
                vocab_size=64)


@pytest.fixture(scope="module")
def model_params():
    return init_gpt_real(CFG, 1)


class TestEnginePrefix:

    def test_engine_rows_share_the_prefix(self, model_params):
        """Engine with a shared system-prompt prefix: each admission
        prefills only its suffix; outputs equal Generator-with-prefix."""
        model, params = model_params
        gen = Generator(model, params, CFG, batch_size=1,
                        prompt_buckets=[16], prefill_chunk=8)
        prefix = np.array([9, 9, 8, 7, 6], np.int32)
        handle = gen.cache_prefix(prefix)
        engine = ContinuousBatchingEngine(gen, max_batch=2,
                                          prompt_bucket=16,
                                          prefix=handle)
        try:
            suffixes = [np.array([1, 2], np.int32),
                        np.array([5, 4, 3], np.int32),
                        np.array([7], np.int32)]
            want = [gen.generate([s], GenerationConfig(max_new_tokens=5),
                                 prefix=handle)[0] for s in suffixes]
            res = [None] * 3

            def do(i):
                res[i] = engine.submit(suffixes[i],
                                       GenerationConfig(max_new_tokens=5))

            ts = [threading.Thread(target=do, args=(i,)) for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for i in range(3):
                np.testing.assert_array_equal(res[i], want[i])
        finally:
            engine.shutdown()

    def test_prefix_engine_guards(self, model_params):
        model, params = model_params
        gen_nochunk = Generator(model, params, CFG, prompt_buckets=[16])

        class _H:
            length = 3
            params = None
        with pytest.raises(ValueError, match="prefill_chunk"):
            ContinuousBatchingEngine(gen_nochunk, prefix=_H())
        # a stale/foreign handle is rejected
        gen_c = Generator(model, params, CFG, prompt_buckets=[16],
                          prefill_chunk=8)
        with pytest.raises(ValueError, match="different params"):
            ContinuousBatchingEngine(gen_c, prefix=_H())


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
