"""Accelerator auto-detection.

Analog of ``accelerator/real_accelerator.py:51`` (get_accelerator) with the
``DS_ACCELERATOR`` env override (reference ``:59``).
"""

import os

from ..utils.logging import logger

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu"]

ds_accelerator = None


def _validate_accelerator(accel_name):
    if accel_name not in SUPPORTED_ACCELERATOR_LIST:
        raise ValueError(f"accelerator name {accel_name} not in supported list {SUPPORTED_ACCELERATOR_LIST}")


def is_current_accelerator_supported():
    return get_accelerator()._name in SUPPORTED_ACCELERATOR_LIST


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    accelerator_name = None
    if "DS_ACCELERATOR" in os.environ:
        accelerator_name = os.environ["DS_ACCELERATOR"]
        _validate_accelerator(accelerator_name)
    else:
        # a backend that fails to come up is an error, not "cpu": the CPU
        # is asked for explicitly (JAX_PLATFORMS=cpu, as the tests do)
        import jax
        platforms = {d.platform for d in jax.devices()}
        accelerator_name = "tpu" if "tpu" in platforms else "cpu"

    from .tpu_accelerator import CPU_Accelerator, TPU_Accelerator
    if accelerator_name == "tpu":
        ds_accelerator = TPU_Accelerator()
    else:
        ds_accelerator = CPU_Accelerator()
    logger.info(f"Setting ds_accelerator to {ds_accelerator._name}")
    return ds_accelerator


def set_accelerator(accel_obj):
    global ds_accelerator
    ds_accelerator = accel_obj
    return ds_accelerator
