"""Model families: dense LLaMA-style (transformer.py), MoE (moe.py) and
the latent-attention, routed-expert, multi-stream family (latent_moe.py,
``cfg.latent``) and the state-space / attention / routed-expert hybrid
(hybrid_ssm.py) and the state-space / window-attention / shared-K/V
hybrid (shared_kv_hybrid.py), each named by ``cfg.family``; the last three
serve through the paged pool only, and the two that keep a recurrent row
a slot (``cfg.hybrid``) have no cold prefill: every prompt goes through
the chunk program, which carries the state.

``model_module(cfg)`` dispatches on ModelConfig.num_experts so the engine,
trainer, and checkpoint code serve either family through one surface:
both modules expose ``init_params(cfg, seed)``, ``prefill`` (MoE returns an
extra aux-loss scalar — use ``serving_prefill`` to normalize), and
``decode_step``; cache layout and the tied LM head live in transformer.py
and are shared.
"""

from __future__ import annotations

from ..config import ModelConfig
from . import (hybrid_ssm, latent_moe, moe, shared_kv_hybrid,  # noqa: F401
               transformer)


def model_module(cfg: ModelConfig):
    module = {"latent": latent_moe, "shared_kv": shared_kv_hybrid,
              "hybrid": hybrid_ssm}.get(cfg.family)
    return module or (moe if cfg.num_experts > 1 else transformer)


def serving_prefill(cfg: ModelConfig, params, tokens, positions, attn=None):
    """(hidden, (k_all, v_all)) for either family (drops MoE aux loss);
    the latent family gives (hidden, (rows,)), one array a pool array.
    ``attn`` (dense only): attention-op override — see transformer.prefill."""
    if cfg.hybrid:
        raise NotImplementedError(
            f"{cfg.name}: the state-space hybrid family has no cold "
            f"prefill; the engine chunk-prefills every prompt")
    if cfg.latent:
        out = latent_moe.prefill(cfg, params, tokens, positions)
    elif cfg.num_experts > 1:
        out = moe.prefill(cfg, params, tokens, positions)
    else:
        out = transformer.prefill(cfg, params, tokens, positions, attn=attn)
    return out[0], out[1]


def init_params(cfg: ModelConfig, seed: int = 0):
    return model_module(cfg).init_params(cfg, seed)
