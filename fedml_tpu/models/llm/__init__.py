"""The LLM path's models. One seam chooses between them: a configuration
object answers ``cfg.module()`` with its flax module, and
:func:`config_from_args` picks the configuration class from the ``model``
a user's yaml names.

A family is added as a file beside ``llama.py``, ``zaya.py``,
``nemotron_h.py`` and ``glm_moe_lite.py`` that edits no shared file: a
configuration (``PRESETS``, ``YAML_FIELDS`` and a ``from_args`` over
:func:`preset_from_args`), a block under the protocol at the top of
``causal_lm.py`` built from ``layers.py``, a ``class
<Family>ForCausalLM(CausalLM)`` that names the block — and a branch in
:func:`config_from_args`. ``docs/llm_finetune.md`` ("Adding a model
family") has the fields, and the reference and tests it comes with.
"""
from __future__ import annotations

from typing import Any, Optional


def config_from_args(args: Any, vocab_size: Optional[int] = None):
    """``ZayaConfig`` for ``model: zaya``, ``NemotronHConfig`` for ``model:
    nemotron_h``, ``GlmMoeLiteConfig`` for ``model: glm4_moe_lite``, else
    ``LlamaConfig``."""
    model = str(getattr(args, "model", "")).lower()
    if model == "zaya":
        from fedml_tpu.models.llm.zaya import ZayaConfig

        return ZayaConfig.from_args(args, vocab_size=vocab_size)
    if model == "nemotron_h":
        from fedml_tpu.models.llm.nemotron_h import NemotronHConfig

        return NemotronHConfig.from_args(args, vocab_size=vocab_size)
    if model == "glm4_moe_lite":
        from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig

        return GlmMoeLiteConfig.from_args(args, vocab_size=vocab_size)
    from fedml_tpu.models.llm.llama import LlamaConfig

    return LlamaConfig.from_args(args, vocab_size=vocab_size)


def preset_from_args(cls, args: Any, vocab_size: Optional[int] = None):
    """The configuration of class ``cls`` a user's yaml asks for:
    ``model_size`` (or ``model_name``) names one of ``cls.PRESETS`` (an
    unknown name is ``tiny``), every field of ``cls.YAML_FIELDS`` the yaml
    sets overrides the preset's, converted to the type of the field's
    default, and three switches are the same for every family:
    ``use_flash_attention``, ``remat_policy``, ``base_params_bf16``. The
    tiny preset takes the data's ``vocab_size`` (at least 32)."""
    import jax.numpy as jnp

    name = str(getattr(args, "model_size", None)
               or getattr(args, "model_name", "tiny")
               ).lower().replace("-", "_")
    preset = cls.PRESETS.get(name, "tiny")
    kw = {}
    for field in cls.YAML_FIELDS:
        if getattr(args, field, None) is not None:
            kw[field] = type(cls.__dataclass_fields__[field].default)(
                getattr(args, field))
    if getattr(args, "use_flash_attention", None) is not None:
        kw["use_flash"] = bool(args.use_flash_attention)
    if getattr(args, "remat_policy", None) is not None:
        kw["remat_policy"] = str(args.remat_policy)
    if bool(getattr(args, "base_params_bf16", False)):
        kw["param_dtype"] = jnp.bfloat16
    if vocab_size is not None and preset == "tiny":
        kw["vocab_size"] = max(vocab_size, 32)
    # a preset's own values are defaults: what the yaml says wins
    return getattr(cls, preset)(**kw)
