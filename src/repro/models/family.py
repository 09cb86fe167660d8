"""Pluggable ``ModelFamily`` protocol + registry — the FL stack's model API.

DR-FL's dual selection runs over *layer-wise* models: a global model that
factors into depth-prefix submodels Model_1..Model_M, each with its own
early exit.  Everything the FL layers (client updates, aggregation masks,
stack templates, the bucketed executor, cost calibration) need from a model
is captured here as one protocol, so `repro.fl` and `repro.core.aggregation`
never import a concrete architecture:

* :class:`ModelFamily` — the abstract surface (init / apply_all_exits /
  masks / stacked-aggregation layout / per-method client updates / cost
  model).
* :class:`LayerwiseFamily` — the shared implementation for any family whose
  parameters follow the canonical layer-wise tree layout
  ``{"stem": ..., "stages": [stage_0, ...], "exits": [exit_0, ...]}``
  (submodel m = stem + stages[:m+1] + exits[:m+1]).  Masks, stack groups,
  templates, SGD client updates and the paper-scale cost model are all
  generic over that layout; concrete families supply ``init``,
  ``apply_all_exits`` and an analytic ``flops_per_sample``.
* the registry — ``register_family`` / ``get_family`` / ``resolve_family``.
  ``"cnn"`` (:class:`repro.models.cnn.CnnFamily`) is the registered default;
  ``"mlp"`` (:class:`repro.models.mlp.MlpFamily`) is the early-exit MLP
  built from :mod:`repro.models.layers`; ``"transformer"``
  (:class:`repro.models.transformer_family.TransformerFamily`) is the
  early-exit decoder trained on the synthetic next-token corpus.

Families are stateful singletons: they own the jitted per-method step
programs and the mask / stack-template caches, so two call sites asking for
the same family share compiled programs (the engine and the frozen
reference loop trace the SAME jitted functions — that is what keeps the
sync-parity contract bit-for-bit).

Public surface (one-line contracts):

* :class:`ModelFamily` — the abstract protocol: ``init`` /
  ``apply_all_exits`` / ``num_submodels`` (model surface), ``submodel_*``
  (depth-prefix views + size accounting), ``update_mask`` /
  ``stack_groups`` / ``stack_template`` / ``held_groups`` /
  ``unstack_groups`` (aggregation layout), ``loss_fn`` /
  ``client_update`` / ``bucket_trace_context`` /
  ``bucket_clients_in_turn`` / ``samples_from_rows`` (client training),
  ``cost_model`` (paper-scale Eq. 5/7 calibration),
  ``state_summary_width`` / ``fleet_summary`` (the factored QMIX global
  state, sized by the family not the fleet), ``supports`` /
  ``supported_methods`` (method gating).
* :class:`LayerwiseFamily` — everything above implemented generically for
  the canonical ``{"stem", "stages": [...], "exits": [...]}`` layout;
  subclasses supply ``init`` / ``apply_all_exits`` / ``num_submodels`` /
  ``flops_per_sample``.
* :func:`register_family` — add a singleton to the registry (key =
  ``family.name`` unless overridden).
* :func:`known_families` — sorted registry keys (builtins auto-load).
* :func:`get_family` — registry lookup; ``None`` -> the default family.
* :func:`resolve_family` — accept name / instance / None uniformly.
* :func:`cross_entropy` — mean CE over a batch (shared loss primitive).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation
from repro.core.baselines import kd_loss


# ---------------------------------------------------------------------------
# shared loss primitives
# ---------------------------------------------------------------------------


def cross_entropy(logits, y):
    """Mean CE over a batch (log-sum-exp form, integer labels)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32),
                              axis=-1)[:, 0]
    return jnp.mean(lse - tgt)


def _mean_loss(losses) -> float:
    """ONE host sync for a whole local run: per-step device scalars stay
    un-synced and are reduced on device; only the final mean crosses."""
    if not losses:
        return 0.0
    # jaxlint: allow(host-sync-in-hot-path) -- the documented one pull per local run: device-reduced mean loss
    return float(jnp.mean(jnp.stack(losses)))


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class ModelFamily:
    """Abstract model-family surface consumed by ``repro.fl``.

    Concrete families are registered singletons (hash by identity — they are
    safe as jit static arguments)."""

    #: registry key / display name
    name: str = "abstract"
    #: FL methods (client-update kinds) this family can train
    supported_methods: Tuple[str, ...] = ()
    #: image size the paper-scale energy model is calibrated at
    ref_hw: int = 32

    # -- model surface ---------------------------------------------------
    def init(self, key, num_classes: int = 10, width_mult: float = 1.0,
             hw: int = 32):
        raise NotImplementedError

    def num_submodels(self) -> int:
        raise NotImplementedError

    def apply_all_exits(self, params, x):
        """Logits from every exit held by ``params`` (truncated trees ok)."""
        raise NotImplementedError

    def flops_per_sample(self, model_idx: int, image_hw: int = 32,
                         width_mult: float = 1.0) -> float:
        """Analytic forward FLOPs for Model_{idx+1} (energy-model input)."""
        raise NotImplementedError

    # -- data surface ------------------------------------------------------
    def make_dataset(self, n: int, num_classes: int = 10, hw: int = 32,
                     noise: float = 1.0, seed: int = 0):
        """The training corpus this family learns from: ``(x, y)`` numpy
        arrays whose ROWS the FL stack treats opaquely (Dirichlet shards by
        label ``y``, row-gathers mini-batches, feeds ``x`` straight to
        ``apply_all_exits``).  Default: the synthetic class-conditional
        image set (``x [n, hw, hw, 3]`` float32); token families override
        with ``[n, seq]`` int32 context windows whose next-token label is
        the class — ``hw`` doubles as the sequence length there."""
        from repro.data.synthetic import synthetic_image_dataset
        return synthetic_image_dataset(n, num_classes, hw=hw, noise=noise,
                                       seed=seed)

    # -- submodel structure ----------------------------------------------
    def submodel_tree(self, tree, model_idx: int):
        """Depth-prefix view of ``tree`` a Model_{idx+1} client trains."""
        raise NotImplementedError

    def submodel_params(self, method: str, global_params, model_idx: int):
        """The initial tree a ``method`` client at ``model_idx`` trains."""
        raise NotImplementedError

    def submodel_size_bytes(self, params, model_idx: int) -> int:
        raise NotImplementedError

    # -- aggregation layout ----------------------------------------------
    def update_mask(self, global_params, model_idx: int, scale: float = 1.0):
        raise NotImplementedError

    def stack_groups(self, params) -> List:
        """Aggregation-unit group trees, in global group order."""
        raise NotImplementedError

    def held_groups(self, global_params, model_idx: int) -> List[bool]:
        """Which global groups a Model_{idx+1} submodel holds."""
        raise NotImplementedError

    def unstack_groups(self, global_params, groups: List):
        """Rebuild a full tree from updated group trees."""
        raise NotImplementedError

    def stack_template(self, global_params, seg: int = 1024):
        raise NotImplementedError

    # -- client training -------------------------------------------------
    def loss_fn(self, method: str) -> Callable:
        raise NotImplementedError

    def client_update(self, method: str, global_params, model_idx: int,
                      x, y, *, epochs: int = 5, batch: int = 32,
                      lr: float = 0.05, seed: int = 0):
        raise NotImplementedError

    def bucket_trace_context(self):
        """Context manager active while the bucketed-vmap executor traces
        this family's forward pass (families may swap in vmap-friendly
        formulations, e.g. the CNN's patches-conv on CPU)."""
        return contextlib.nullcontext()

    def bucket_clients_in_turn(self) -> bool:
        """Whether a bucket program runs its clients one after another,
        each for its own steps only, instead of vmapping a masked scan
        over padded lanes.  Worth it where one client's step already fills
        the device, so lanes buy nothing and pad steps cost whole passes."""
        return False

    def samples_from_rows(self, params, rows):
        """A batch gathered from the training set held as rows ``[B, D]``
        (``repro.fl.batch.resident``, where clients run in turn) as the
        samples ``params``' model takes.  Samples that are rows already
        (feature vectors, token sequences) pass unchanged."""
        return rows

    # -- cost model -------------------------------------------------------
    def cost_model(self, num_classes: int = 10
                   ) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
        """(submodel bytes, FLOP fractions) at PAPER scale (width 1.0,
        ``ref_hw`` images) — what the Eq. 5/7 energy accounting charges."""
        raise NotImplementedError

    # -- factored MARL state ----------------------------------------------
    def state_summary_width(self, n_bins: Optional[int] = None) -> int:
        """Width of this family's factored QMIX global state
        (:func:`repro.core.fleet.summary_width` over its submodel count) —
        a function of the FAMILY, independent of ``n_devices``.  This is
        the registry hook the scaled MARL selector sizes its mixer with."""
        from repro.core import fleet as core_fleet
        bins = core_fleet.SUMMARY_BINS if n_bins is None else n_bins
        return core_fleet.summary_width(self.num_submodels(), bins)

    def fleet_summary(self, fleet, round_idx=0, n_rounds: int = 1, *,
                      num_classes: int = 10, local_epochs: int = 5,
                      batch_size: int = 32):
        """Fixed-width fleet summary priced with THIS family's Eq. 5/7
        cost model (per-submodel affordability fractions use the family's
        paper-scale sizes/FLOP fractions) — see
        :func:`repro.core.fleet.fleet_summary`."""
        from repro.core import fleet as core_fleet
        sizes, fractions = self.cost_model(num_classes)
        return core_fleet.fleet_summary(
            fleet, sizes, fractions, round_idx, n_rounds,
            local_epochs, batch_size)

    def supports(self, method: str) -> bool:
        return method in self.supported_methods

    def __repr__(self):
        return f"<ModelFamily {self.name!r}>"


# ---------------------------------------------------------------------------
# generic layer-wise implementation (canonical stem/stages/exits layout)
# ---------------------------------------------------------------------------


class LayerwiseFamily(ModelFamily):
    """Shared machinery for families with the canonical layer-wise layout.

    Parameters are ``{"stem": tree, "stages": [tree...], "exits": [tree...]}``
    with one exit per stage; submodel m trains stem + stages[:m+1] +
    exits[:m+1] (deep supervision over every held exit).  Aggregation
    groups are stem + each stage + each exit — the units
    :meth:`update_mask` masks as wholes and the stacked Pallas path
    flattens into segment rows.
    """

    supported_methods = ("drfl",)

    def __init__(self):
        # mask pytrees depend only on tree STRUCTURE and (model_idx, scale);
        # leaves are immutable jnp scalars, safe to alias between calls
        self._mask_cache: dict = {}
        self._template_cache: dict = {}
        self._cost_cache: dict = {}
        self._jit_cache: dict = {}

    # -- submodel structure ----------------------------------------------
    def submodel_tree(self, tree, model_idx: int):
        return {"stem": tree["stem"],
                "stages": tree["stages"][:model_idx + 1],
                "exits": tree["exits"][:model_idx + 1]}

    def submodel_params(self, method: str, global_params, model_idx: int):
        if method == "drfl":
            return self.submodel_tree(global_params, model_idx)
        raise ValueError(f"family {self.name!r} does not support "
                         f"method {method!r} (supported: "
                         f"{self.supported_methods})")

    def _size_tree(self, params, model_idx: int):
        """The pytree a Model_{idx+1} client actually holds on device for
        size accounting: depth prefix + ITS exit head only."""
        return {"stem": params["stem"],
                "stages": params["stages"][:model_idx + 1],
                "exits": [params["exits"][model_idx]]}

    def submodel_size_bytes(self, params, model_idx: int) -> int:
        tree = self._size_tree(params, model_idx)
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))

    # -- aggregation layout ----------------------------------------------
    def update_mask(self, global_params, model_idx: int, scale: float = 1.0):
        """Scalar masks matching the layer-wise tree: stem + stages<=m +
        exits<=m (clients deep-supervise every exit their submodel holds).
        ``scale`` replaces the 1.0 of held layers — the staleness path
        builds decay masks (value alpha_s per exit-layer) with the same
        structure."""
        key = (jax.tree.structure(global_params), int(model_idx),
               float(scale))
        hit = self._mask_cache.get(key)
        if hit is not None:
            return hit

        def const(tree, v):
            return jax.tree.map(lambda _: jnp.asarray(v, jnp.float32), tree)

        mask = {
            "stem": const(global_params["stem"], scale),
            "stages": [const(s, scale if i <= model_idx else 0.0)
                       for i, s in enumerate(global_params["stages"])],
            "exits": [const(e, scale if i <= model_idx else 0.0)
                      for i, e in enumerate(global_params["exits"])],
        }
        if len(self._mask_cache) > 512:     # staleness scales are open-ended
            self._mask_cache.clear()
        self._mask_cache[key] = mask
        return mask

    def stack_groups(self, params) -> List:
        return ([params["stem"]] + list(params["stages"])
                + list(params["exits"]))

    def held_groups(self, global_params, model_idx: int) -> List[bool]:
        n_stages = len(global_params["stages"])
        held = [i <= model_idx for i in range(n_stages)]
        return [True] + held + held

    def unstack_groups(self, global_params, groups: List):
        n_stages = len(global_params["stages"])
        return {"stem": groups[0],
                "stages": groups[1:1 + n_stages],
                "exits": groups[1 + n_stages:]}

    def stack_template(self, global_params, seg: int = 1024):
        shapes = tuple((tuple(l.shape), str(l.dtype))
                       for l in jax.tree.leaves(global_params))
        key = (shapes, int(seg))
        if key not in self._template_cache:
            self._template_cache[key] = aggregation.build_stack_template(
                self.stack_groups(global_params), seg=seg)
        return self._template_cache[key]

    # -- losses -----------------------------------------------------------
    def _drfl_loss(self, sub, x, y):
        """Joint CE over every exit the submodel holds (BranchyNet-style
        deep supervision); the deepest held exit carries full weight,
        shallower exits get 0.3."""
        outs = self.apply_all_exits(sub, x)
        loss = cross_entropy(outs[-1], y)
        for o in outs[:-1]:
            loss = loss + 0.3 * cross_entropy(o, y)
        return loss / (1.0 + 0.3 * (len(outs) - 1))

    def _slice_loss(self, sub, x, y):
        """Width-sliced trees (HeteroFL): loss at the deepest exit."""
        outs = self.apply_all_exits(sub, x)
        return cross_entropy(outs[-1], y)

    def _scalefl_loss(self, sub, x, y):
        """Depth+width tree; CE at every held exit + KD deepest->shallower."""
        outs = self.apply_all_exits(sub, x)
        teacher = outs[-1]
        loss = cross_entropy(teacher, y)
        for s in outs[:-1]:
            loss = loss + 0.5 * (cross_entropy(s, y)
                                 + kd_loss(s, jax.lax.stop_gradient(teacher)))
        return loss / max(len(outs), 1)

    def loss_fn(self, method: str) -> Callable:
        try:
            return {"drfl": self._drfl_loss,
                    "heterofl": self._slice_loss,
                    "scalefl": self._scalefl_loss}[method]
        except KeyError:
            raise ValueError(f"unknown method {method!r}") from None

    # -- jitted per-method SGD steps --------------------------------------
    def _step_fn(self, method: str):
        key = ("step", method)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        loss_fn = self.loss_fn(method)
        if method == "drfl":
            # jaxlint: allow(retrace-hazard) -- memoised in self._jit_cache keyed by (step, method); built once per family
            @functools.partial(jax.jit, static_argnums=(3,))
            def fn(params, x, y, model_idx: int, lr: float = 0.05):
                def wrapped(p):
                    return loss_fn(self.submodel_tree(p, model_idx), x, y)

                loss, grads = jax.value_and_grad(wrapped)(params)
                new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
                return new, loss
        else:
            # jaxlint: allow(retrace-hazard) -- memoised in self._jit_cache keyed by (step, method); built once per family
            @jax.jit
            def fn(params, x, y, lr: float = 0.05):
                loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
                new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
                return new, loss
        self._jit_cache[key] = fn
        return fn

    def eval_fn(self):
        """Jitted per-exit accuracy over one batch (server evaluation)."""
        fn = self._jit_cache.get("eval")
        if fn is None:
            # jaxlint: allow(retrace-hazard) -- memoised in self._jit_cache under "eval"; built once per family
            @jax.jit
            def fn(params, x, y):
                outs = self.apply_all_exits(params, x)
                return jnp.stack([jnp.mean((jnp.argmax(o, -1) == y))
                                  for o in outs])
            self._jit_cache["eval"] = fn
        return fn

    # -- client training --------------------------------------------------
    def client_update(self, method: str, global_params, model_idx: int,
                      x, y, *, epochs: int = 5, batch: int = 32,
                      lr: float = 0.05, seed: int = 0):
        """One client's local run: returns ``(delta, mean local loss)``.

        ``method="drfl"`` trains the depth-prefix submodel *in place* on
        the full-structure tree (grads are exactly zero outside the
        submodel, so the returned delta is already zero-filled for
        layer-aligned aggregation); other methods train the family's
        sliced submodel tree and return the sliced delta."""
        from repro.data.loader import epoch_batches
        if not self.supports(method):
            raise ValueError(f"family {self.name!r} does not support "
                             f"method {method!r} (supported: "
                             f"{self.supported_methods})")
        rng = np.random.default_rng(seed)
        step = self._step_fn(method)
        if method == "drfl":
            params = global_params
            losses = []
            for _ in range(epochs):
                for xb, yb in epoch_batches(x, y, batch, rng):
                    params, l = step(params, jnp.asarray(xb),
                                     jnp.asarray(yb), model_idx, lr)
                    losses.append(l)
            delta = jax.tree.map(lambda a, b: a - b, params, global_params)
            return delta, _mean_loss(losses)
        sub = self.submodel_params(method, global_params, model_idx)
        params, losses = sub, []
        for _ in range(epochs):
            for xb, yb in epoch_batches(x, y, batch, rng):
                params, l = step(params, jnp.asarray(xb), jnp.asarray(yb),
                                 lr)
                losses.append(l)
        delta = jax.tree.map(lambda a, b: a - b, params, sub)
        return delta, _mean_loss(losses)

    # -- cost model --------------------------------------------------------
    def cost_model(self, num_classes: int = 10):
        """Paper-scale calibration: submodel sizes from an eval_shape init
        at width 1.0 / ``ref_hw`` (no arrays materialized), FLOP fractions
        from the analytic per-sample forward cost."""
        key = int(num_classes)
        hit = self._cost_cache.get(key)
        if hit is not None:
            return hit
        M = self.num_submodels()
        ref = jax.eval_shape(
            lambda k: self.init(k, num_classes, width_mult=1.0,
                                hw=self.ref_hw),
            jax.random.PRNGKey(0))
        sizes = tuple(
            sum(l.size * l.dtype.itemsize
                for l in jax.tree.leaves(self._size_tree(ref, m)))
            for m in range(M))
        full = self.flops_per_sample(M - 1, self.ref_hw, 1.0)
        fractions = tuple(self.flops_per_sample(m, self.ref_hw, 1.0) / full
                          for m in range(M))
        self._cost_cache[key] = (sizes, fractions)
        return sizes, fractions


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ModelFamily] = {}
_DEFAULT = "cnn"
_BUILTINS_LOADED = False


def register_family(family: ModelFamily,
                    name: Optional[str] = None) -> ModelFamily:
    """Register a family singleton under ``name`` (default: family.name)."""
    key = name or family.name
    _REGISTRY[key] = family
    return family


def _ensure_builtins():
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    # concrete families self-register at import; imported lazily so the
    # registry module itself stays import-cycle-free
    from repro.models import cnn, mlp, transformer_family  # noqa: F401


def known_families() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def get_family(name: Optional[str] = None) -> ModelFamily:
    _ensure_builtins()
    key = name or _DEFAULT
    try:
        return _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown model family {key!r} "
            f"(registered: {', '.join(sorted(_REGISTRY))})") from None


def resolve_family(family=None) -> ModelFamily:
    """None -> the default family; str -> registry lookup; a ModelFamily
    instance passes through."""
    if family is None:
        return get_family()
    if isinstance(family, str):
        return get_family(family)
    if isinstance(family, ModelFamily):
        return family
    raise TypeError(f"expected ModelFamily, name or None, got {family!r}")
