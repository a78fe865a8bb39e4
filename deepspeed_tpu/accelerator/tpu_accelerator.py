"""TPU accelerator. Analog of ``accelerator/cuda_accelerator.py`` for TPU/XLA."""

import functools

from .abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"  # ICI/DCN via XLA collectives

    def is_available(self):
        import jax
        return any(d.platform == "tpu" for d in jax.devices())

    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device_count(self):
        import jax
        return jax.device_count()

    def devices(self):
        import jax
        return jax.devices()

    def memory_stats(self, device_index=None):
        import jax
        devs = jax.local_devices()
        idx = device_index or 0
        if idx < len(devs):
            try:
                return devs[idx].memory_stats() or {}
            except Exception:
                return {}
        return {}

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True  # computed, not native — bf16 is the fast path

    def is_fp8_supported(self):
        # v5p+/v6 support fp8 matmuls; conservatively probe dtype availability
        import jax.numpy as jnp
        return hasattr(jnp, "float8_e4m3fn")

    def communication_backend_name(self):
        return self._communication_backend_name

    def op_builder_dir(self):
        return "deepspeed_tpu.ops.op_builder.tpu"

    @functools.lru_cache(None)
    def _builder_registry(self):
        from ..ops.op_builder import ALL_OPS
        return ALL_OPS

    def create_op_builder(self, class_name):
        builder = self.get_op_builder(class_name)
        return builder() if builder is not None else None

    def get_op_builder(self, class_name):
        return self._builder_registry().get(class_name)


class CPU_Accelerator(TPU_Accelerator):
    """Host-CPU accelerator (tests, offload targets). XLA:CPU backs compute."""

    def __init__(self):
        super().__init__()
        self._name = "cpu"
        self._communication_backend_name = "gloo"  # name parity; actual transport is XLA

    def is_available(self):
        return True

    def device_name(self, device_index=None):
        return "cpu"

    def is_bf16_supported(self):
        return True

    def is_fp8_supported(self):
        return False

    def op_builder_dir(self):
        return "deepspeed_tpu.ops.op_builder.cpu"
