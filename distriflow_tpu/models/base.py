"""Core model abstraction.

TPU-native re-design of the reference's ``DistributedModel`` interface
(``src/common/models.ts:7-72``): ``fit(x,y)->grads``, ``update(grads)``,
``predict``, ``evaluate``, ``get_params``/``set_params``, ``input_shape``/
``output_shape``.

Two levels, by design:

- :class:`ModelSpec` — the *functional* core trainers consume: pure
  ``init``/``apply``/``loss`` functions over a params pytree. This is the
  idiomatic JAX shape (everything jit-able, params explicit); the reference
  has no equivalent because tfjs models are inherently stateful.
- :class:`DistributedModel` — the *stateful parity API* matching the
  reference's surface, built on a ModelSpec. Gradient<->param correspondence
  is by pytree structure, making explicit the positional invariant the
  reference leaves implicit (``src/common/models.ts:140``, key-order vs
  trainableWeights order).

``fit`` computes gradients but does NOT apply them — the reference's
contract (client computes, server applies; ``src/common/models.ts:137-142``).
``update`` applies the optimizer step (plain SGD ``v <- v - lr*g`` by
default, ``src/common/models.ts:128-135``).
"""

from __future__ import annotations

import abc
import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distriflow_tpu.models import losses as losses_lib
from distriflow_tpu.utils.config import CompileConfig

Params = Any  # a pytree of arrays
Batch = Tuple[jnp.ndarray, jnp.ndarray]


def _optimizer(
    name: Union[str, optax.GradientTransformation],
    learning_rate: Union[None, float, Callable[[Any], Any]],
    default_rate: float = 0.001,
) -> optax.GradientTransformation:
    """Optimizer registry. The reference hardcodes 'sgd' (``models.ts:88``);
    here sgd is the parity default and the registry is open via optax.

    ``name`` may also be a ready-made ``optax.GradientTransformation``
    (bring any chain), and ``learning_rate`` may be an optax schedule
    (step -> lr), e.g. from ``distriflow_tpu.train.schedules``. ``None``
    means "unset": the caller's ``default_rate`` applies (the reference
    client default 0.001, ``src/common/utils.ts:183``), and no
    ignored-rate warning can fire when a ready-made transformation is
    supplied.

    **Frozen-param convention**: every returned transform — registry-built
    or ready-made — is wrapped in ``optax.masked`` excluding params whose
    leaf name starts with ``frozen_`` (e.g. ``FrozenBatchNorm``'s
    ``frozen_mean``/``frozen_var``). stop_gradient alone zeroes their
    grads but cannot stop gradient-independent updates like adamw's
    decoupled weight decay, which would silently decay pretrained
    statistics toward zero. NB the wrapper adds a ``MaskedState`` level to
    the opt-state pytree, so opt-state checkpoints written by versions
    without it do not restore (structure is path-keyed and mismatches
    raise loudly).
    """
    if isinstance(name, optax.GradientTransformation):
        if learning_rate is not None:
            # the rate lives inside the chain; an explicit learning_rate
            # would be silently dropped — say so
            warnings.warn(
                "learning_rate is ignored when passing a ready-made optax "
                "transformation — set the rate inside the chain instead",
                stacklevel=2,
            )
        return optax.masked(name, _trainable_mask)
    if learning_rate is None:
        learning_rate = default_rate
    registry: Dict[str, Callable[[Any], optax.GradientTransformation]] = {
        "sgd": optax.sgd,
        "momentum": lambda lr: optax.sgd(lr, momentum=0.9),
        "adam": optax.adam,
        "adamw": optax.adamw,
        "rmsprop": optax.rmsprop,
        "adagrad": optax.adagrad,
    }
    if name not in registry:
        raise KeyError(f"unknown optimizer {name!r}; registered: {sorted(registry)}")
    return optax.masked(registry[name](learning_rate), _trainable_mask)


def _trainable_mask(tree: Any) -> Any:
    """True for trainable leaves; False where the LEAF NAME starts with
    ``frozen_`` (an exact-prefix test on the final path component — a
    module merely containing the substring, e.g. ``UnfrozenEncoder``,
    still trains)."""

    def trainable(path, _):
        last = path[-1] if path else None
        name = getattr(last, "key", None)
        if name is None:
            name = getattr(last, "name", "")
        return not str(name).startswith("frozen_")

    return jax.tree_util.tree_map_with_path(trainable, tree)


def jitted_metrics(holder: Any, spec: "ModelSpec", metrics: Tuple[str, ...]):
    """One compiled metrics program per metric tuple, cached on ``holder``
    (all three trainers share this — a fresh ``jax.jit`` per evaluate call
    would recompile on every chunk of ``train.evaluate_dataset``)."""
    cache = getattr(holder, "_eval_fns", None)
    if cache is None:
        cache = holder._eval_fns = {}
    key = tuple(metrics)
    if key not in cache:
        cache[key] = jax.jit(spec.metrics_fn(list(key)))
    return cache[key]


def init_params(spec: "ModelSpec", rng: jax.Array) -> Params:
    """Run ``spec.init`` under jit, falling back to eager.

    Eager init executes one op at a time — one dispatch per parameter
    tensor where the jitted form is a single program (cost of either on the
    current machine: not measured). Trainers funnel through here so every
    model family gets the single-dispatch path; non-traceable inits (custom
    host-side logic) silently keep eager semantics.
    """
    try:
        return jax.jit(spec.init)(rng)
    except Exception as e:
        import warnings

        warnings.warn(
            f"jitted init of {spec.name!r} failed ({type(e).__name__}: {e}); "
            "falling back to eager init — correct but one dispatch per op",
            stacklevel=2,
        )
        return spec.init(rng)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Pure-functional model: the unit trainers, servers, and clients share.

    ``apply(params, x)`` returns predictions/logits. ``loss`` is a registry
    name resolved through ``distriflow_tpu.models.losses`` (fixing the
    reference bug where the configured loss was ignored,
    ``src/common/models.ts:139``).
    """

    init: Callable[[jax.Array], Params]  # rng -> params
    apply: Callable[[Params, jnp.ndarray], jnp.ndarray]
    loss: str = "softmax_cross_entropy"
    input_shape: Tuple[int, ...] = ()
    output_shape: Tuple[int, ...] = ()
    name: str = "model"
    # optional single-forward variant returning (preds, aux_scalar); the aux
    # term (e.g. an MoE router load-balancing loss) is added to the training
    # loss but excluded from eval metrics. Must compute the SAME preds as
    # ``apply`` — it exists so auxiliary losses ride the one forward pass
    # instead of a second one.
    apply_with_aux: Optional[Callable[[Params, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]] = None

    def loss_fn(
        self,
        params: Params,
        x: jnp.ndarray,
        y: jnp.ndarray,
        weight: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Weighted-mean loss; ``weight`` (per-example, 0 for padding rows)
        makes padded partial batches exact on a sharded mesh.

        Caveat: exactness covers the primary loss term. Models whose forward
        pass has batch-coupled internals (MoE capacity routing — padding rows
        still route and count in the load-balance statistics) are exact only
        up to that coupling; mask at the data layer if it matters."""
        loss = losses_lib.get_loss(self.loss)
        if self.apply_with_aux is not None:
            preds, aux = self.apply_with_aux(params, x)
            return loss(preds, y, weight) + aux
        preds = self.apply(params, x)
        if isinstance(preds, (tuple, list)):
            # multi-output model (e.g. an imported multi-head Keras graph):
            # total loss = sum of per-output losses (Keras's default
            # reduction); targets must arrive as a matching tuple
            if not isinstance(y, (tuple, list)) or len(y) != len(preds):
                raise ValueError(
                    f"model has {len(preds)} outputs; targets must be a "
                    f"{len(preds)}-tuple, got {type(y).__name__}"
                )
            total = loss(preds[0], y[0], weight)
            for p, t in zip(preds[1:], y[1:]):
                total = total + loss(p, t, weight)
            return total
        return loss(preds, y, weight)

    def grad_fn(self) -> Callable[..., Tuple[jnp.ndarray, Params]]:
        """(params, x, y[, weight]) -> (loss, grads). Jit-compiled by callers."""
        return jax.value_and_grad(self.loss_fn)

    def metrics_fn(self, metric_names: Sequence[str]) -> Callable[..., List[jnp.ndarray]]:
        loss = losses_lib.get_loss(self.loss)

        def compute(
            params: Params,
            x: jnp.ndarray,
            y: jnp.ndarray,
            weight: Optional[jnp.ndarray] = None,
        ) -> List[jnp.ndarray]:
            preds = self.apply(params, x)
            out = []
            for m in metric_names:
                if m == "loss":
                    out.append(loss(preds, y, weight))
                else:
                    out.append(losses_lib.get_metric(m)(preds, y, weight))
            return out

        return compute


class DistributedModel(abc.ABC):
    """Stateful parity surface (reference ``DistributedModel``,
    ``src/common/models.ts:7-72``)."""

    @abc.abstractmethod
    def fit(self, x: jnp.ndarray, y: jnp.ndarray) -> Params:
        """Compute gradients on a batch WITHOUT applying them."""

    @abc.abstractmethod
    def update(self, grads: Params) -> None:
        """Apply one optimizer step with the given gradients."""

    @abc.abstractmethod
    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        ...

    @abc.abstractmethod
    def evaluate(self, x: jnp.ndarray, y: jnp.ndarray) -> List[float]:
        ...

    @abc.abstractmethod
    def get_params(self) -> Params:
        ...

    @abc.abstractmethod
    def set_params(self, params: Params) -> None:
        ...

    @property
    @abc.abstractmethod
    def input_shape(self) -> Tuple[int, ...]:
        ...

    @property
    @abc.abstractmethod
    def output_shape(self) -> Tuple[int, ...]:
        ...

    def setup(self) -> None:
        """Async-init hook (reference ``fetchInitial``); default no-op."""


class SpecModel(DistributedModel):
    """DistributedModel over a ModelSpec + resident params.

    The common concrete implementation behind both the 'layers-model' (C2)
    and 'dynamic' (C3) wrappers. All compute paths are jit-compiled once and
    cached; params live on device.
    """

    def __init__(
        self,
        spec: ModelSpec,
        compile_config: Optional[CompileConfig] = None,
        learning_rate: Optional[float] = None,  # None -> 0.001 (reference default)
        params: Optional[Params] = None,
        rng: Optional[jax.Array] = None,
    ):
        self.spec = spec
        self.compile_config = compile_config or CompileConfig()
        if self.compile_config.loss is not None and self.compile_config.loss != spec.loss:
            # honor an explicitly-configured loss over the spec default (the
            # reference silently ignored it; src/common/models.ts:139)
            self.spec = dataclasses.replace(spec, loss=self.compile_config.loss)
        self.learning_rate = 0.001 if learning_rate is None else learning_rate
        self._params = params
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._optimizer = _optimizer(self.compile_config.optimizer, learning_rate)
        self._opt_state = None
        # jit caches
        self._jit_grad = jax.jit(self.spec.grad_fn())
        self._jit_apply = jax.jit(self.spec.apply)
        self._jit_metrics = jax.jit(self.spec.metrics_fn(["loss", *self.compile_config.metrics]))

        def _apply_update(params: Params, opt_state: Any, grads: Params):
            updates, new_opt_state = self._optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt_state

        self._jit_update = jax.jit(_apply_update)
        self.last_loss: Optional[float] = None

    # -- lifecycle ---------------------------------------------------------

    def setup(self) -> None:
        if self._params is None:
            self._params = self.spec.init(self._rng)
        if self._opt_state is None:
            self._opt_state = self._optimizer.init(self._params)

    def _ensure_setup(self) -> None:
        if self._params is None or self._opt_state is None:
            self.setup()

    # -- DistributedModel surface -----------------------------------------

    def fit(self, x: jnp.ndarray, y: jnp.ndarray) -> Params:
        self._ensure_setup()
        loss, grads = self._jit_grad(self._params, x, y)
        self.last_loss = float(loss)
        return grads

    def update(self, grads: Params) -> None:
        self._ensure_setup()
        self._params, self._opt_state = self._jit_update(self._params, self._opt_state, grads)

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        self._ensure_setup()
        return self._jit_apply(self._params, x)

    def evaluate(self, x: jnp.ndarray, y: jnp.ndarray) -> List[float]:
        self._ensure_setup()
        return [float(v) for v in self._jit_metrics(self._params, x, y)]

    def get_params(self) -> Params:
        self._ensure_setup()
        return self._params

    def set_params(self, params: Params) -> None:
        self._params = jax.tree.map(jnp.asarray, params)
        if self._opt_state is None:
            self._opt_state = self._optimizer.init(self._params)

    @property
    def input_shape(self) -> Tuple[int, ...]:
        return tuple(self.spec.input_shape)

    @property
    def output_shape(self) -> Tuple[int, ...]:
        return tuple(self.spec.output_shape)


def with_uint8_inputs(
    spec: ModelSpec, scale: float = 1.0 / 255.0, offset: float = 0.0
) -> ModelSpec:
    """Wire-format adapter: the model accepts raw uint8 inputs and
    normalizes on device (``x * scale + offset`` after a float32 cast).

    Streaming pixels as uint8 cuts host->device bytes 4x vs float32 — where
    the input stream, not compute, binds a small model's throughput (the
    host->device rate of the current machine: not measured). Pair with
    integer labels + a sparse loss to shrink the label stream too.
    """

    def norm(x: jnp.ndarray) -> jnp.ndarray:
        if jnp.issubdtype(x.dtype, jnp.floating):
            # already-normalized floats would be silently re-scaled by
            # 1/255 — a near-certain wire-format mix-up; fail at trace time
            raise TypeError(
                f"with_uint8_inputs got {x.dtype} input; this spec expects "
                "raw integer pixels (feed the un-normalized uint8 stream, "
                "or use the base spec for float inputs)"
            )
        return x.astype(jnp.float32) * scale + offset

    apply = spec.apply
    new = dataclasses.replace(spec, apply=lambda p, x: apply(p, norm(x)))
    if spec.apply_with_aux is not None:
        with_aux = spec.apply_with_aux
        new = dataclasses.replace(
            new, apply_with_aux=lambda p, x: with_aux(p, norm(x))
        )
    return new


ModelSource = Union[ModelSpec, DistributedModel, Callable[[], "ModelSpec"], str]


def fetch_model(source: ModelSource, **kw: Any) -> DistributedModel:
    """Resolve a model source to a DistributedModel.

    Parity with reference ``fetchModel`` (``src/common/utils.ts:236-244``),
    which accepts a string URL, a model instance, or an async factory. Here:
    a ModelSpec, an existing DistributedModel, a zero-arg factory returning a
    ModelSpec, a tfjs-layers/Keras ``model.json`` path (the reference's
    ``tf.loadLayersModel`` equivalent, via
    :func:`distriflow_tpu.models.keras_import.spec_from_keras_json`), or a
    checkpoint-directory path string (loaded via ``distriflow_tpu.checkpoint``).
    """
    if isinstance(source, DistributedModel):
        return source
    if isinstance(source, ModelSpec):
        return SpecModel(source, **kw)
    if callable(source):
        spec = source()
        if not isinstance(spec, ModelSpec):
            raise TypeError(f"model factory must return a ModelSpec, got {type(spec)}")
        return SpecModel(spec, **kw)
    if isinstance(source, str):
        if source.startswith(("http://", "https://")):
            # the reference's string-URL source: tf.loadLayersModel(url)
            # with URL-relative weight shards (utils.ts:236-244)
            from distriflow_tpu.models import keras_import

            spec_kw = {
                k: kw.pop(k)
                for k in ("input_shape", "loss", "logits_output", "load_weights", "dtype")
                if k in kw
            }
            return SpecModel(keras_import.spec_from_url(source, **spec_kw), **kw)
        if source.endswith((".json", ".h5", ".hdf5")):
            from distriflow_tpu.models import keras_import

            parse = (keras_import.spec_from_keras_json if source.endswith(".json")
                     else keras_import.spec_from_keras_h5)
            spec_kw = {
                k: kw.pop(k)
                for k in ("input_shape", "loss", "logits_output", "load_weights", "dtype")
                if k in kw
            }
            return SpecModel(parse(source, **spec_kw), **kw)
        from distriflow_tpu.checkpoint import load_model  # lazy: layer dependency

        return load_model(source, **kw)
    raise TypeError(f"cannot resolve model source of type {type(source)}")
