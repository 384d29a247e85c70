"""TPU-native distributed Stable Diffusion framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
papuSpartan/stable-diffusion-webui-distributed: where the reference shards a
batched txt2img/img2img request across a pool of CUDA-backed sdwui HTTP workers
(reference: scripts/distributed.py, scripts/spartan/world.py), this framework
runs the entire diffusion pipeline in-process as Flax modules compiled by XLA
and shards the batch across a TPU mesh via ``shard_map``/``pjit``, with the
reference's World/Job/ETA/benchmark scheduling policy reborn as a multi-slice
planner and an sdapi-v1-compatible serving surface on top.

Import convention::

    import stable_diffusion_webui_distributed_tpu as sdt
"""

__version__ = "0.1.0"

# before anything allocates much (runtime/malloc.py says why)
from stable_diffusion_webui_distributed_tpu.runtime.malloc import (
    retain_freed_memory,
)

retain_freed_memory()

# Short, stable aliases for the most-used entry points. Heavy submodules
# (models, pipeline) are imported lazily by callers to keep CLI startup fast.
from stable_diffusion_webui_distributed_tpu.runtime.logging import get_logger  # noqa: F401
from stable_diffusion_webui_distributed_tpu.runtime.config import (  # noqa: F401
    BenchmarkPayload,
    ConfigModel,
    WorkerModel,
)
