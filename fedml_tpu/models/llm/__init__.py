"""The LLM path's models. One seam chooses between them: a configuration
object answers ``cfg.module()`` with its flax module, and
:func:`config_from_args` picks the configuration class from the ``model``
a user's yaml names."""
from __future__ import annotations

from typing import Any, Optional


def config_from_args(args: Any, vocab_size: Optional[int] = None):
    """``ZayaConfig`` for ``model: zaya``, else ``LlamaConfig``."""
    if str(getattr(args, "model", "")).lower() == "zaya":
        from fedml_tpu.models.llm.zaya import ZayaConfig

        return ZayaConfig.from_args(args, vocab_size=vocab_size)
    from fedml_tpu.models.llm.llama import LlamaConfig

    return LlamaConfig.from_args(args, vocab_size=vocab_size)
