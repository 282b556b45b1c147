"""Model serving: generation engine + HTTP controller.

Analog of ref ``alpa/serve/`` + ``examples/llm_serving`` (SURVEY.md §2.8,
§3.5): a controller with a model registry dispatching to replicas, and an
autoregressive generation engine with resident KV caches compiled per
(batch, length-bucket).
"""
from alpa_tpu.serve.generation import (BlockDiffusion, GenerationConfig,
                                       Generator, PrefixHandle, get_model)
from alpa_tpu.serve.controller import (Controller, ControllerServer,
                                       RequestBatcher, run_controller)
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.hf_wrapper import WrappedInferenceModel, get_hf_model
from alpa_tpu.serve.kv_cache import (KVBlockPool, KVPoolExhaustedError,
                                     PagedSequence)
from alpa_tpu.serve.router import (HTTPReplicaHandle, LocalReplicaHandle,
                                   Router, RouterServer)
from alpa_tpu.serve.scheduler import (FIFOQueue, NestedScheduler,
                                      WeightedFairQueue)
