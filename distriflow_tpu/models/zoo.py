"""Model zoo: the benchmark-config model families.

- :func:`mnist_mlp` — parity with the reference experiment's 2-dense softmax
  MLP (``createDenseModel``: flatten -> dense(10, relu) -> dense(10, softmax),
  ``experiment/mnist/mnist_server.ts:16-22``). We keep logits un-softmaxed
  (softmax lives inside the CE loss — numerically superior and MXU-friendly);
  hidden width configurable.
- :func:`mnist_convnet` — the Keras ConvNet the reference ships as
  ``experiment/mnist/model.json`` (Conv2D x2 + MaxPool + dense head).
- :func:`cifar_convnet` — CIFAR-10 ConvNet (``experiments/cifar10``).
- MobileNetV2 lives in ``distriflow_tpu/models/mobilenet.py``; the
  transformer (long-context flagship) in ``distriflow_tpu/models/transformer.py``.
- :func:`flagship_lm_config` / :func:`draft_lm_config` — the small/flagship
  LM pairing the serving engine uses as draft/target for speculative
  decoding (``ServingConfig.speculate_k``; docs/PERFORMANCE.md §7g).
  :func:`draft_config_for` resolves ``ServingConfig.draft_model`` names and
  forces the fields a draft MUST share with its target.

All models compute in a configurable dtype (default float32; pass
``jnp.bfloat16`` to target the MXU's native precision).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

from distriflow_tpu.models.base import ModelSpec
from distriflow_tpu.models.flax_model import spec_from_flax
from distriflow_tpu.models.transformer import TransformerConfig


class MLP(nn.Module):
    """flatten -> dense(hidden, relu) -> dense(classes) logits."""

    hidden: int = 10
    classes: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        x = nn.Dense(self.hidden, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Dense(self.classes, dtype=self.dtype)(x)
        return x


class ConvNet(nn.Module):
    """Conv stack + dense head (reference ``experiment/mnist/model.json`` family)."""

    features: Sequence[int] = (32, 64)
    classes: int = 10
    dense: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        for f in self.features:
            x = nn.Conv(f, kernel_size=(3, 3), dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.dense, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Dense(self.classes, dtype=self.dtype)(x)
        return x


def mnist_mlp(hidden: int = 10, dtype: Any = jnp.float32) -> ModelSpec:
    """The MNIST parity model (reference ``mnist_server.ts:16-22``)."""
    return spec_from_flax(
        MLP(hidden=hidden, classes=10, dtype=dtype),
        input_shape=(28, 28, 1),
        output_shape=(10,),
        name="mnist_mlp",
    )


def mnist_convnet(dtype: Any = jnp.float32) -> ModelSpec:
    """Reference ``experiment/mnist/model.json`` ConvNet family."""
    return spec_from_flax(
        ConvNet(features=(32, 64), classes=10, dense=128, dtype=dtype),
        input_shape=(28, 28, 1),
        output_shape=(10,),
        name="mnist_convnet",
    )


def cifar_convnet(dtype: Any = jnp.float32) -> ModelSpec:
    """The CIFAR-10 model of ``experiments/cifar10`` (sync and async)."""
    return spec_from_flax(
        ConvNet(features=(64, 128, 256), classes=10, dense=256, dtype=dtype),
        input_shape=(32, 32, 3),
        output_shape=(10,),
        name="cifar_convnet",
    )


# -- LM pairing for speculative decoding (docs/PERFORMANCE.md §7g) ----------


def flagship_lm_config(max_seq: int = 2048,
                       dtype: Any = jnp.bfloat16) -> TransformerConfig:
    """The zoo's mid-size LM (d512, 8 layers, vocab 32000) as a serving
    target config; :func:`draft_lm_config` is its speculative draft."""
    return TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
        max_seq=max_seq, dtype=dtype)


def draft_lm_config(max_seq: int = 2048,
                    dtype: Any = jnp.bfloat16) -> TransformerConfig:
    """The zoo's small LM: ~1/20th the flagship's FLOPs per token (2
    layers at a quarter width), sized so k draft steps cost well under
    one target step — the regime where speculation can win."""
    return TransformerConfig(
        vocab_size=32000, d_model=128, n_heads=4, n_layers=2, d_ff=512,
        max_seq=max_seq, dtype=dtype)


#: ``ServingConfig.draft_model`` names -> config factories. ``"self"`` is
#: resolved by :func:`draft_config_for` (the target config itself:
#: self-speculation, acceptance ~= k by construction — the mechanical
#: ceiling the serving_speculative bench row measures).
_DRAFT_LMS = {"lm_draft": draft_lm_config}


def draft_config_for(name: str,
                     target: TransformerConfig) -> TransformerConfig:
    """Resolve a ``ServingConfig.draft_model`` name against a target
    config. The draft keeps its own depth/width but is forced onto the
    fields a draft/target pair MUST share for verification to be
    meaningful and for the page-table geometry to line up: vocab (token
    ids must mean the same thing), ``max_seq`` (page-table width), dtype
    and attention-kernel toggles (so both halves compile for the same
    backend)."""
    if name == "self":
        return target
    factory = _DRAFT_LMS.get(name)
    if factory is None:
        raise ValueError(
            f"unknown draft_model {name!r}; known: "
            f"{sorted(_DRAFT_LMS) + ['self']}")
    draft = factory(max_seq=target.max_seq, dtype=target.dtype)
    return dataclasses.replace(
        draft,
        vocab_size=target.vocab_size,
        max_seq=target.max_seq,
        dtype=target.dtype,
        use_flash_attention=target.use_flash_attention,
        use_flash_decode=target.use_flash_decode,
    )
