"""The model's equations, one module per architecture.

A configuration file names its architecture under ``"arch"``; without the
key it is ``"dense"``.  ``load(m)`` returns ``bench/arch/<arch>.py``, which
provides everything of the harness that depends on the model's equations:

- ``SIZE_KEYS``: the file's entries handed to the program's ``ModelConfig``;
- ``make_weights(m, dtype, seed)``: the parameter tree in the program's
  layout, drawn from ``bench.weights.key_of(seed)`` on the device in one
  jitted call;
- ``served_gaps(mk, params, tokens, valid, quant, kv, split)`` and
  ``loss_and_grad(mk, params, batch, z_loss, rows, quant, block)``: the
  plain float32 reference for serving and for training (``mk`` is
  ``bench.reference.sizes_key(m)``), scored with the shared Hyft datapath
  of ``bench/reference.py``;
- ``layers(m)``: one :class:`Layer` per layer, which the work counts of
  ``bench/costs.py`` sum over;
- ``active_matmul_params(m)``: the matrix-product weights of one block that
  one token uses.

A new architecture is a new module here and nothing else.
"""
from __future__ import annotations

import importlib
from typing import NamedTuple, Optional


class Layer(NamedTuple):
    """The attention of one layer: query heads, KV heads, head width, and
    the keys a query sees (``None``: every earlier position; else the last
    ``window`` positions, its own included)."""
    heads: int
    kv_heads: int
    d_head: int
    window: Optional[int] = None


def load(m: dict):
    """The architecture module of configuration ``m``."""
    return importlib.import_module(f"{__name__}.{m.get('arch', 'dense')}")
