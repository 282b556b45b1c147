"""alpa_tpu: TPU-native automatic parallelization for jax programs.

A ground-up redesign of Alpa (alpa-projects/alpa) for TPU pods: automatic
inter-operator (pipeline) + intra-operator (sharding) parallelization on top
of stock jax/XLA — GSPMD over ICI for intra-mesh collectives, jax-runtime
DCN transfers for cross-mesh resharding, no forked jaxlib, no Ray.
See SURVEY.md for the design blueprint.
"""
from alpa_tpu.api import (clear_executable_cache, init, shutdown,
                          parallelize, grad, value_and_grad)
from alpa_tpu.device_mesh import (DeviceCluster, DistributedArray,
                                  DistributedPhysicalDeviceMesh,
                                  LocalPhysicalDeviceMesh, LogicalDeviceMesh,
                                  PhysicalDeviceMesh, PhysicalDeviceMeshGroup,
                                  VirtualPhysicalMesh,
                                  get_global_cluster,
                                  get_global_num_devices,
                                  get_global_physical_mesh,
                                  get_global_virtual_physical_mesh,
                                  prefetch,
                                  set_global_physical_mesh,
                                  set_global_virtual_physical_mesh, set_seed)
from alpa_tpu.global_env import global_config
from alpa_tpu.parallel_method import (DataParallel, LocalPipelineParallel,
                                      ParallelMethod, PipeshardParallel,
                                      ShardParallel, Zero2Parallel,
                                      Zero3Parallel, get_3d_parallel_method)
from alpa_tpu.create_state_parallel import CreateStateParallel
from alpa_tpu.data_loader import (DataLoader, DistributedDataLoader,
                                  MeshDriverDataLoader)
from alpa_tpu.follow_parallel import FollowParallel
from alpa_tpu.parallel_plan import (ParallelPlan, executable_to_plan,
                                    plan_to_method)
from alpa_tpu.mesh_profiling import ProfilingResultDatabase
from alpa_tpu.pipeline_parallel.layer_construction import (AutoLayerOption,
                                                           ManualLayerOption,
                                                           automatic_remat,
                                                           manual_remat)
from alpa_tpu.pipeline_parallel.primitive_def import (mark_pipeline_boundary)
from alpa_tpu.pipeline_parallel.stage_construction import (AutoStageOption,
                                                           ManualStageOption,
                                                           UniformStageOption)
from alpa_tpu import fault
from alpa_tpu.serialization import (restore_checkpoint, save_checkpoint)
from alpa_tpu.checkpoint import (CheckpointManager, RecoveryCheckpointer,
                                 RetentionPolicy)
from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
from alpa_tpu.shard_parallel.manual_sharding import ManualShardingOption
from alpa_tpu.timer import timers, tracer

__version__ = "0.1.0"
