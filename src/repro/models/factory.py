"""Model factory: ArchConfig -> model instance."""

from __future__ import annotations

from typing import Optional

import jax

from repro.configs.base import ArchConfig
from repro.models.encdec import EncDecModel
from repro.models.transformer import Model


def build_model(
    cfg: ArchConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    q_chunk: int = 1024,
):
    if cfg.enc_dec:
        return EncDecModel(cfg, mesh, q_chunk)
    return Model(cfg, mesh, q_chunk)
