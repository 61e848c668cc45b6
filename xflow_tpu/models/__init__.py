"""Model registry — the ONE place a family plugs into the system.

The reference dispatches models by positional index (main.cc:27-45,
argv[3] '0'→LR '1'→FM '2'→MVM); this repo's five-then-seven families
used to be re-enumerated as string literals in config validation, the
CLI choices and the C-ABI docs — adding a family meant a scavenger
hunt.  Now a family registers HERE once:

* ``build`` — Config -> Model instance (the only constructor callers
  use; serve/engine.py, trainer.py, the C ABI all route through
  ``make_model``);
* ``retrieval`` — the family factors into user/item towers
  (``user_embed``/``item_embed``) whose item side exports a serve-time
  top-k index (serve/artifact.py::export_item_index,
  PredictEngine.topk).  Non-retrieval families refuse the index/top-k
  surface with an actionable error instead of scoring garbage.

``Config.__post_init__`` validates ``cfg.model`` against
``model_names()`` and ``xflow_tpu.train`` builds its ``--model``
choices from it — so a new family is config-valid, CLI-reachable and
C-ABI-servable by virtue of this one entry.  It is MEASURED when a
``model_config`` PR gives it a configuration and a cell under
``benchmarks/`` (BENCHMARK.json), not before.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from xflow_tpu.models.autoint import AutoIntModel
from xflow_tpu.models.base import AutodiffModel, Model, TableSpec
from xflow_tpu.models.dcn import DCNModel
from xflow_tpu.models.ffm import FFMModel
from xflow_tpu.models.fibinet import FiBiNETModel
from xflow_tpu.models.fm import FMModel
from xflow_tpu.models.lr import LRModel
from xflow_tpu.models.mvm import MVMModel
from xflow_tpu.models.two_tower import TwoTowerModel
from xflow_tpu.models.wide_deep import WideDeepModel
from xflow_tpu.models.xdeepfm import XDeepFMModel


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    build: Callable[..., Model]  # (cfg: Config) -> Model
    description: str
    #: user/item-tower factorization: item tower exports a serve-time
    #: top-k index (serve/artifact.py, PredictEngine.topk)
    retrieval: bool = False


REGISTRY: dict[str, ModelFamily] = {}


def register_model(family: ModelFamily) -> ModelFamily:
    """Add a family (refuses duplicate names — two registrations for
    one name is always a bug, not an override)."""
    if family.name in REGISTRY:
        raise ValueError(f"model family {family.name!r} already registered")
    REGISTRY[family.name] = family
    return family


def model_names() -> tuple[str, ...]:
    return tuple(REGISTRY)


def model_family(name: str) -> ModelFamily:
    fam = REGISTRY.get(name)
    if fam is None:
        raise ValueError(
            f"unknown model {name!r} (registered families: "
            f"{', '.join(REGISTRY)})"
        )
    return fam


def make_model(cfg) -> Model:
    # Reference model dispatch: main.cc:27-45, argv[3] '0'→LR '1'→FM
    # '2'→MVM; everything else is a capability extension registered
    # above the reference's zoo.
    return model_family(cfg.model).build(cfg)


register_model(ModelFamily(
    "lr", lambda cfg: LRModel(),
    "sparse logistic regression (reference model 0)",
))
register_model(ModelFamily(
    "fm",
    lambda cfg: FMModel(v_dim=cfg.v_dim, v_init_scale=cfg.v_init_scale),
    "2-way factorization machine (reference model 1)",
))
register_model(ModelFamily(
    "mvm",
    lambda cfg: MVMModel(
        v_dim=cfg.v_dim,
        v_init_scale=cfg.v_init_scale,
        max_fields=cfg.max_fields,
    ),
    "multi-view machine (reference model 2)",
))
register_model(ModelFamily(
    "ffm",
    lambda cfg: FFMModel(
        v_dim=cfg.ffm_v_dim,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "field-aware FM (extension; BASELINE.json target)",
))
register_model(ModelFamily(
    "wide_deep",
    lambda cfg: WideDeepModel(
        emb_dim=cfg.emb_dim,
        hidden=cfg.hidden_dim,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "wide & deep: sparse linear + embedding MLP (extension)",
))
register_model(ModelFamily(
    "two_tower",
    lambda cfg: TwoTowerModel(
        emb_dim=cfg.emb_dim,
        tower_dim=cfg.tower_dim,
        hidden=cfg.hidden_dim,
        max_fields=cfg.max_fields,
        split_field=cfg.tower_split_field,
        v_init_scale=cfg.v_init_scale,
    ),
    "two-tower retrieval: dot-product user/item towers over disjoint "
    "field groups; item tower exports the serve-time top-k index",
    retrieval=True,
))
register_model(ModelFamily(
    "dcn",
    lambda cfg: DCNModel(
        emb_dim=cfg.emb_dim,
        hidden=cfg.hidden_dim,
        cross_layers=cfg.cross_layers,
        deep_layers=cfg.deep_layers,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "deep & cross ranker: explicit bounded-degree feature crosses + "
    "MLP over the embedding tower (the cascade's ranking stage)",
))
register_model(ModelFamily(
    "xdeepfm",
    lambda cfg: XDeepFMModel(
        emb_dim=cfg.emb_dim,
        cin_maps=cfg.cin_maps,
        cross_layers=cfg.cross_layers,
        hidden=cfg.hidden_dim,
        deep_layers=cfg.deep_layers,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "xDeepFM ranker: a compressed interaction network (vector-wise "
    "crosses of bounded degree) beside an MLP over the embedding tower",
))
register_model(ModelFamily(
    "autoint",
    lambda cfg: AutoIntModel(
        emb_dim=cfg.emb_dim,
        attn_heads=cfg.attn_heads,
        attn_dim=cfg.attn_dim,
        cross_layers=cfg.cross_layers,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "AutoInt ranker: multi-head self-attention over the fields of a row "
    "(per-example interactions, softmax over the fields the row has)",
))
register_model(ModelFamily(
    "fibinet",
    lambda cfg: FiBiNETModel(
        emb_dim=cfg.emb_dim,
        senet_reduction=cfg.senet_reduction,
        hidden=cfg.hidden_dim,
        deep_layers=cfg.deep_layers,
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    ),
    "FiBiNET ranker: a SENET gate a field computed from all of a row's "
    "fields, and a learned matrix for every pair of fields on the plain and "
    "on the gated embeddings, under an MLP",
))


def _build_dlrm(cfg) -> Model:
    # imported where it is built: a process that trains or serves another
    # family never loads models/dlrm.py
    from xflow_tpu.config import mlp_widths
    from xflow_tpu.models.dlrm import DLRMModel

    return DLRMModel(
        emb_dim=cfg.emb_dim,
        numeric_fields=cfg.numeric_fields,
        mlp_bottom=mlp_widths(cfg.mlp_bottom, "mlp_bottom"),
        mlp_top=mlp_widths(cfg.mlp_top, "mlp_top"),
        max_fields=cfg.max_fields,
        v_init_scale=cfg.v_init_scale,
    )


register_model(ModelFamily(
    "dlrm", _build_dlrm,
    "DLRM ranker: a ReLU stack over the real-valued fields, embeddings of "
    "the categorical ones, the dot of every pair of those vectors, a ReLU "
    "stack over the dots (Config.numeric_fields, mlp_bottom, mlp_top)",
))


__all__ = [
    "AutodiffModel",
    "Model",
    "ModelFamily",
    "REGISTRY",
    "TableSpec",
    "LRModel",
    "FMModel",
    "MVMModel",
    "FFMModel",
    "WideDeepModel",
    "TwoTowerModel",
    "DCNModel",
    "XDeepFMModel",
    "AutoIntModel",
    "FiBiNETModel",
    "make_model",
    "model_family",
    "model_names",
    "register_model",
]
