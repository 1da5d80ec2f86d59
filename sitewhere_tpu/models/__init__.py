"""Model zoo registry.

Tenant templates (``runtime.config``) name models by key; the tpu-inference
engine resolves them here. Scorer models share one contract:

    cfg    = spec.config_cls(**model_config_overrides)
    params = spec.init(key, cfg)
    scores = spec.score(params, cfg, windows[B, W], n_valid[B])  # f32[B]

which is what lets heterogeneous tenants stack along the mesh tenant axis
as long as they share a model *family* (SURVEY.md §7 "tenants-on-mesh").
A family whose stream state is not a window (``nemotron_h``) brings the
stateful contract instead — ``init_state`` / ``advance``, see ``ModelSpec``.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Callable, Dict, Optional, Tuple

from sitewhere_tpu.models import (
    afmoe,
    deepar,
    lfm2_moe,
    lstm_ad,
    nemotron_h,
    transformer,
    vit,
)
from sitewhere_tpu.models.common import (
    DEFAULT_SCORE_RANGE,
    deepar_flops_per_row,
    lstm_ad_flops_per_row,
    param_count,
    transformer_flops_per_row,
    vit_flops_per_image,
)

__all__ = [
    "ModelSpec",
    "MODEL_REGISTRY",
    "get_model",
    "make_config",
    "param_count",
    "lstm_ad",
    "deepar",
    "transformer",
    "vit",
    "nemotron_h",
    "afmoe",
    "lfm2_moe",
]


@dataclass(frozen=True)
class ModelSpec:
    name: str
    config_cls: type
    init: Callable
    score: Optional[Callable] = None      # scorer contract (windows, n_valid)
    # fused megabatch contract (models.common; parallel.sharded fused
    # step): (stacked_params, cfg, windows[S,B,W], n_valid[S,B], k=K)
    # → f32[S,B,K] via ONE wide einsum per contraction over the stacked
    # plane. None = family runs the legacy vmap-over-slots path only.
    score_stacked: Optional[Callable] = None
    loss: Optional[Callable] = None
    # stacked training contract (models.common; parallel.sharded fused
    # train step): (stacked_params, cfg, windows[S,B,W]) → per-row loss
    # f32[S,B] through the same weight-stacked einsums as score_stacked,
    # so grads lower slot-count-invariant too. None = family trains via
    # the legacy per-slot vmap only (and never rides the train lane).
    loss_stacked: Optional[Callable] = None
    forecast: Optional[Callable] = None
    apply: Optional[Callable] = None      # classifier contract (images)
    train_step: Optional[Callable] = None
    # analytic matmul FLOPs to score ONE row (or classify one image) at a
    # given series-window length — the device-time/MFU attribution
    # contract (models.common; docs/PERFORMANCE.md "MFU accounting")
    flops_per_row: Optional[Callable] = None
    # (lo, hi) score range for the device-side score sketch's log-spaced
    # bin edges (models.common.sketch_edges; docs/OBSERVABILITY.md "Score
    # health & canaries") — the zoo's |error|-in-sigma scorers share the
    # default; a family with different score units overrides it here
    score_range: Tuple[float, float] = DEFAULT_SCORE_RANGE
    # stateful scorer contract (parallel.streamstate): a family whose
    # per-stream state is NOT a window of raw values brings
    #   init_state(cfg, max_streams) -> one slot's state pytree, every
    #     leaf led by the stream axis;
    #   advance(params, cfg, state, ids[N], toks[N, L], lens[N],
    #     one_step=bool) -> (state', scores f32[N, L], counters i32[3])
    # — score each token from the state stored BEFORE it, then advance.
    # ``score`` stays None: there is no window to re-scan.
    #   state_traffic(cfg, streams, tokens) -> (bytes read, written)
    #     of stream state by calls advancing that many streams / tokens
    init_state: Optional[Callable] = None
    advance: Optional[Callable] = None
    state_traffic: Optional[Callable] = None
    # a family whose store also holds leaves led by a PAGE axis (a pool
    # the slot's streams share, handed out by the host) brings
    #   page_pool(cfg) -> parallel.streamstate.PageLayout
    # and its ``advance`` takes ``pages=i32[N, 1 + table]``: each row's
    # first logical page held, then its physical pages in order
    page_pool: Optional[Callable] = None


MODEL_REGISTRY: Dict[str, ModelSpec] = {
    "lstm_ad": ModelSpec(
        name="lstm_ad",
        config_cls=lstm_ad.LstmAdConfig,
        init=lstm_ad.init,
        score=lstm_ad.score,
        score_stacked=lstm_ad.score_stacked,
        loss=lstm_ad.loss,
        loss_stacked=lstm_ad.loss_stacked,
        train_step=lstm_ad.train_step,
        flops_per_row=lstm_ad_flops_per_row,
    ),
    "deepar": ModelSpec(
        name="deepar",
        config_cls=deepar.DeepArConfig,
        init=deepar.init,
        score=deepar.score,
        score_stacked=deepar.score_stacked,
        loss=deepar.loss,
        loss_stacked=deepar.loss_stacked,
        forecast=deepar.forecast,
        train_step=deepar.train_step,
        flops_per_row=deepar_flops_per_row,
    ),
    "transformer": ModelSpec(
        name="transformer",
        config_cls=transformer.TransformerForecasterConfig,
        init=transformer.init,
        score=transformer.score,
        score_stacked=transformer.score_stacked,
        loss=transformer.loss,
        loss_stacked=transformer.loss_stacked,
        forecast=transformer.forecast,
        train_step=transformer.train_step,
        flops_per_row=transformer_flops_per_row,
    ),
    "nemotron_h": ModelSpec(
        name="nemotron_h",
        config_cls=nemotron_h.NemotronHConfig,
        init=nemotron_h.init,
        init_state=nemotron_h.init_state,
        advance=nemotron_h.advance,
        state_traffic=nemotron_h.state_traffic,
        flops_per_row=nemotron_h.flops_per_row,
        score_range=nemotron_h.SCORE_RANGE,
    ),
    "afmoe": ModelSpec(
        name="afmoe",
        config_cls=afmoe.AfmoeConfig,
        init=afmoe.init,
        init_state=afmoe.init_state,
        advance=afmoe.advance,
        page_pool=afmoe.page_pool,
        flops_per_row=afmoe.flops_per_row,
        score_range=afmoe.SCORE_RANGE,
    ),
    "lfm2_moe": ModelSpec(
        name="lfm2_moe",
        config_cls=lfm2_moe.Lfm2MoeConfig,
        init=lfm2_moe.init,
        init_state=lfm2_moe.init_state,
        advance=lfm2_moe.advance,
        page_pool=lfm2_moe.page_pool,
        flops_per_row=lfm2_moe.flops_per_row,
        score_range=lfm2_moe.SCORE_RANGE,
    ),
    "vit_b16": ModelSpec(
        name="vit_b16",
        config_cls=vit.ViTConfig,
        init=vit.init,
        apply=vit.apply,
        loss=vit.loss,
        train_step=vit.train_step,
        flops_per_row=vit_flops_per_image,
    ),
}


def get_model(name: str) -> ModelSpec:
    try:
        return MODEL_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model '{name}' (known: {sorted(MODEL_REGISTRY)})"
        ) from None


def make_config(name: str, overrides: Optional[Dict[str, Any]] = None):
    """Build a model config from a template's ``model_config`` dict,
    ignoring unknown keys (forward-compatible tenant templates)."""
    spec = get_model(name)
    known = {f.name for f in fields(spec.config_cls)}
    kwargs = {k: v for k, v in (overrides or {}).items() if k in known}
    return spec.config_cls(**kwargs)
