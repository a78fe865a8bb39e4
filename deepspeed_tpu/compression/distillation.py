"""Knowledge-distillation hooks for compression training.

Analog of the reference's distillation stage (``init_compression``'s
``teacher_model`` + the kd-loss term the compression tutorials wire into the
training loop; XTC's recipe prescribes a distillation phase after layer
reduction/binarization). TPU-native shape: the student model is WRAPPED —
its ``loss`` becomes ``(1 - alpha) * CE + alpha * T^2 * KL(teacher || student)``
— so ZeRO/offload/bf16 engine features compose without engine changes.
(The pipeline engine drives ``head_loss`` directly and does not carry the
KD term; distill under DP/ZeRO, as the reference tutorials do.)

Teacher logits enter through the BATCH (``batch["teacher_logits"]``), not a
closed-over teacher forward: closed-over device arrays get baked into the
compiled step as constants (multi-MB programs: slow to compile, and the
constants sit in HBM beside the arrays they copy), and
batch-borne logits let the teacher run anywhere — a separate jit on the
same chip (``make_teacher_provider``), a different host, or offline
precomputation over the dataset (the cheapest classic KD setup).
"""

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp


def kd_loss(student_logits, teacher_logits, temperature: float = 1.0,
            loss_mask=None):
    """Soft-target KL: T^2 * mean_t KL(softmax(t/T) || softmax(s/T)).
    ``loss_mask`` weights positions exactly like the CE term (pad/prompt
    tokens must not pull the student toward the teacher)."""
    t = jnp.asarray(temperature, jnp.float32)
    sl = student_logits.astype(jnp.float32) / t
    tl = teacher_logits.astype(jnp.float32) / t
    p_t = jax.nn.softmax(tl, axis=-1)
    kl = jnp.sum(p_t * (jax.nn.log_softmax(tl, axis=-1)
                        - jax.nn.log_softmax(sl, axis=-1)), axis=-1)
    if loss_mask is None:
        return (t * t) * jnp.mean(kl)
    m = loss_mask.astype(jnp.float32)
    return (t * t) * jnp.sum(kl * m) / jnp.maximum(jnp.sum(m), 1.0)


class DistilledModel:
    """Student wrapper adding the KD term to the loss.

    ``alpha`` mixes hard CE and soft KD; ``temperature`` softens both
    distributions. Batches WITHOUT ``teacher_logits`` fall back to the plain
    student loss (so eval/serving paths are untouched).
    """

    def __init__(self, student, alpha: float = 0.5, temperature: float = 2.0):
        self.student = student
        self.alpha = float(alpha)
        self.temperature = float(temperature)

    @classmethod
    def from_config(cls, student, ds_config: Dict[str, Any]):
        kd = (ds_config.get("compression_training", {})
              .get("knowledge_distillation", {}))
        if not kd.get("enabled", False):
            return student
        return cls(student, alpha=kd.get("alpha", 0.5),
                   temperature=kd.get("temperature", 2.0))

    # engine protocol: delegate everything except loss
    def __getattr__(self, name):
        return getattr(self.student, name)

    def loss(self, params, batch):
        teacher_logits = batch.get("teacher_logits")
        if teacher_logits is None:
            return self.student.loss(params, batch)
        # ONE student forward serves both terms: logit distillation needs
        # the dense logits anyway, so CE is derived from them (+ the MoE
        # router aux the plain loss would carry) instead of a second pass
        from ..models.transformer import masked_token_nll
        s_logits, aux = self.student.apply(
            params, batch["input_ids"], positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"), return_aux_loss=True)
        ce = masked_token_nll(s_logits, batch["labels"],
                              batch.get("loss_mask"))
        cfg = self.student.cfg
        if cfg.is_moe:
            ce = ce + cfg.moe_aux_loss_coef * aux
        kd = kd_loss(s_logits, teacher_logits, self.temperature,
                     loss_mask=batch.get("loss_mask"))
        return (1.0 - self.alpha) * ce + self.alpha * kd


def make_teacher_provider(teacher_model, teacher_params,
                          ) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    """Returns ``augment(batch) -> batch + {"teacher_logits"}``: one jitted
    teacher forward per batch, run OUTSIDE the training step (its output is
    then just another staged batch leaf)."""
    fwd = jax.jit(lambda p, ids: teacher_model.apply(p, ids))

    def augment(batch):
        out = dict(batch)
        out["teacher_logits"] = fwd(teacher_params, batch["input_ids"])
        return out

    return augment
