"""Module system: functional core + stateful Torch-style shell.

Reference equivalent: ``nn/abstractnn/AbstractModule.scala:54`` — Torch-style
modules with mutable ``output``/``gradInput`` caches and explicit
``updateOutput`` / ``updateGradInput`` / ``accGradParameters``.

The TPU-native design inverts this.  Every module defines ONE pure function::

    apply(params, input, state, training=False, rng=None) -> (output, new_state)

- ``params``  : pytree of trainable arrays (a dict for leaves, a list of child
                pytrees for containers);
- ``state``   : pytree of non-trainable buffers (e.g. BatchNormalization
                running statistics); ``{}`` for the common stateless case;
- ``input``   : an *Activity* — a jax array or an arbitrarily nested
                list/tuple/dict of arrays (the reference's ``Table``,
                ``nn/abstractnn/Activity.scala:32``);
- ``rng``     : jax PRNG key for stochastic layers (Dropout, RReLU).

Whole models compose into one pure function, so training steps fuse under a
single ``jax.jit`` + ``jax.value_and_grad`` — XLA sees the entire graph and
schedules it onto the MXU, instead of the reference's layer-at-a-time MKL
dispatch.  The familiar imperative surface (``forward``, ``backward``,
``zero_grad_parameters``, ``get_parameters``) is preserved as a thin shell over
the pure core: ``backward`` is ``jax.vjp`` of ``apply``, gradient accumulation
(the reference's ``accGradParameters``) is a pytree add in the shell.
"""

from __future__ import annotations

import itertools
import pickle
import time
from contextlib import nullcontext
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.engine import Engine

# An Activity is a jax array or a nested list/tuple/dict of them.
Activity = Any
Params = Any
State = Any


def tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_add(a, b):
    if a is None:
        return b
    return jax.tree_util.tree_map(jnp.add, a, b)


def semantic_state_leaves(module, state=None):
    """State leaves of ``module`` excluding per-forward diagnostics: the
    leaves whose values must actually thread across steps.

    A module opts its OWN top-level state keys out by declaring them in
    ``diagnostic_state_keys`` (e.g. MixtureOfExperts' load-balance scalar
    ``aux_loss``) — the exclusion is scoped to that module, not a global
    key-name blocklist, so an unrelated module storing genuine cross-step
    state under the same name still trips "stateless" guards.  Modules
    that nest another module's state under a key (MoE's ``"expert"``)
    declare the mapping in ``state_children`` so the walk recurses with
    the right owner.  ``state`` overrides the module's live state (used
    to check a freshly built sub-state before it is installed)."""
    if state is None:
        module._ensure_init()
        state = module.state
    if isinstance(module, Container):
        return [leaf for child, s in zip(module.children, state)
                for leaf in semantic_state_leaves(child, s)]
    if isinstance(state, dict):
        sub = getattr(module, "state_children", {}) or {}
        diag = getattr(module, "diagnostic_state_keys", ()) or ()
        out = []
        for k, v in state.items():
            if k in diag:
                continue
            if k in sub:
                out.extend(semantic_state_leaves(sub[k], v))
            else:
                out.extend(jax.tree_util.tree_leaves(v))
        return out
    return jax.tree_util.tree_leaves(state)


def collect_diagnostics(module, state, key: str):
    """Collect every DECLARED per-forward diagnostic named ``key`` from a
    state tree, walking modules in parallel (same ownership rules as
    :func:`semantic_state_leaves`).  Trainers use this to fold MoE's
    ``aux_loss`` load-balancing term into the objective — only modules
    that declared the key contribute, so an unrelated state entry with
    the same name is never swept into the loss."""
    out = []
    if isinstance(module, Container):
        for child, s in zip(module.children, state):
            out.extend(collect_diagnostics(child, s, key))
        return out
    if isinstance(state, dict):
        diag = getattr(module, "diagnostic_state_keys", ()) or ()
        sub = getattr(module, "state_children", {}) or {}
        if key in diag and key in state:
            out.append(state[key])
        for k, v in state.items():
            if k in sub:
                out.extend(collect_diagnostics(sub[k], v, key))
    return out


def _child_rng(rng, i: int):
    return None if rng is None else jax.random.fold_in(rng, i)


def child_scope(child: "Module", i: int):
    """The ``jax.named_scope`` a container runs child ``i``'s ``apply``
    under (:meth:`Module.scope_name`): metadata only, so that every device
    operation carries the path of the module it was born in."""
    name = child.scope_name(i)
    return jax.named_scope(name) if name else nullcontext()


class Module:
    """Base class of all layers and containers.

    Subclasses must implement :meth:`_init_params` (and optionally
    :meth:`_init_state`) plus the pure :meth:`apply`.
    """

    _name_seq = itertools.count()
    #: whether ``name`` came from the user (constructor or set_name); a
    #: class default, so modules pickled before it existed read False
    _named = False

    # ---- data-layout contract (channels-last compute path) --------------
    # How this module relates to the data format of image activations
    # (see nn/layout.py, which uses this to move a convnet's interior to
    # the TPU-native NHWC layout while the public API stays NCHW):
    #   "opaque"   — layout-dependent or unknown: must see the Torch-facade
    #                NCHW activations (the safe default);
    #   "agnostic" — elementwise/broadcast: whatever layout flows in flows
    #                out unchanged;
    #   "spatial"  — consumes image maps in ``self.format`` and can be
    #                re-pointed between "NCHW" and "NHWC" via
    #                :meth:`set_format`.
    layout_role = "opaque"

    # ---- declarable IO contract (bigdl_tpu.analysis.contracts) ----------
    # A ModuleContract (input rank(s), dtype policy, promotion expectation)
    # that the static contract checker verifies with jax.eval_shape — no
    # FLOPs.  Class attribute for layer families (conv/pool/BN declare
    # theirs), instance attribute via declare_contract for one-offs.
    contract = None

    def declare_contract(self, **kwargs) -> "Module":
        """Attach a per-instance IO contract, e.g.
        ``m.declare_contract(input_ndim=(2, 3), dtypes="float")`` —
        checked by :func:`bigdl_tpu.analysis.check_model`."""
        from bigdl_tpu.analysis.contracts import ModuleContract
        self.contract = ModuleContract(**kwargs)
        return self

    def __init__(self, name: Optional[str] = None):
        self.name = name or f"{type(self).__name__}_{next(Module._name_seq)}"
        self._named = name is not None
        self.train_mode: bool = True
        # Imperative-shell caches (reference AbstractModule.output/gradInput)
        self.output: Activity = None
        self.grad_input: Activity = None
        # Gradient scaling (reference scaleW/scaleB via setScaleW/setScaleB)
        self.scale_w: float = 1.0
        self.scale_b: float = 1.0
        # Per-layer regularizers, consumed by the training-loss builder
        self.w_regularizer = None
        self.b_regularizer = None
        self._params: Optional[Params] = None
        self._state: Optional[State] = None
        self._last_rng = None
        self._fwd_state_in = None
        self._rng_seq = itertools.count(1)
        self._jit_apply = None
        # forward/backward nanosecond timing (reference AbstractModule:193-204)
        self.forward_time: int = 0
        self.backward_time: int = 0

    def set_name(self, name: str) -> "Module":
        """Name this module (reference ``AbstractModule.setName``): the
        key of ``get_parameters_table`` and the module's named scope."""
        self.name = name
        self._named = True
        return self

    def scope_name(self, index: int) -> Optional[str]:
        """The named scope a container wraps this child's ``apply`` in:
        the user-given name, else child index and class
        (``2_Sequential/0_Linear``) — never the default name, whose
        sequence number is process-wide, so two processes, and two models
        built in a different order, lower to the same scope names.  A
        wrapper whose children name themselves returns None."""
        return self.name if self._named else f"{index}_{type(self).__name__}"

    # ---- pure core ------------------------------------------------------

    def _init_params(self, rng) -> Params:
        """Create the trainable parameter pytree.  ``{}`` if none."""
        return {}

    def _init_state(self) -> State:
        """Create the non-trainable state pytree.  ``{}`` if none."""
        return {}

    def apply(self, params: Params, input: Activity, state: State,
              training: bool = False, rng=None) -> Tuple[Activity, State]:
        """The pure forward function.  MUST be overridden, MUST NOT mutate."""
        raise NotImplementedError(type(self).__name__)

    # ---- parameter lifecycle -------------------------------------------

    # ---- what a model holds per parameter -------------------------------
    #: dtype floating parameters are created at; None leaves each
    #: initialiser's own (float32)
    param_dtype = None
    #: the gradient accumulator of the imperative shell (the reference's
    #: ``gradWeight``), behind :attr:`_grads`.  None until something reads
    #: or adds to it: ``reset()`` allocates parameters and state only, so
    #: a model that is only ever run (``Optimizer.optimize()`` and the
    #: serving engines never read the accumulator) holds its parameters
    #: once.  A class default, so modules pickled before it existed load
    _grads_tree = None

    def set_param_dtype(self, dtype) -> "Module":
        """Create the floating parameters of this module and everything
        below it at ``dtype`` from the next ``reset()`` on (parameters that
        exist are left alone).  ``jnp.bfloat16`` for a model that is
        served in bf16: after ``reset(key)`` it then holds two bytes a
        parameter and nothing else."""
        for m in self.modules():
            m.param_dtype = None if dtype is None else jnp.dtype(dtype)
        return self

    def init_params(self, rng) -> Params:
        """``_init_params`` at :attr:`param_dtype`: what ``reset`` installs."""
        params = self._init_params(rng)
        if self.param_dtype is None:
            return params
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.param_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    def reset(self, rng=None) -> "Module":
        """(Re-)initialise parameters (reference ``reset()``).  Under a
        :attr:`param_dtype` narrower than float32 the initialisers run as
        ONE compiled call on the default device, not leaf by leaf, so no
        float32 copy of the whole tree ever exists beside the narrow one.
        The gradient accumulator is dropped, and allocated again on its
        first use."""
        if rng is None:
            rng = jax.random.PRNGKey(Engine.get_seed() + hash(self.name) % (2 ** 31))
        narrow = any(m.param_dtype is not None and m.param_dtype.itemsize < 4
                     for m in self.modules())
        # a one-off initialisation, not a step: nothing to track or cache
        init = (jax.jit(self.init_params)  # lint: allow(untracked-jit)
                if narrow else self.init_params)
        self._params = init(rng)
        self._state = self._init_state()
        self._grads_tree = None
        self._jit_apply = None
        return self

    @property
    def _grads(self) -> Optional[Params]:
        """The gradient accumulator, zeros on first use (None before the
        parameters exist)."""
        if self._grads_tree is None and self._params is not None:
            self._grads_tree = self._new_grads()
        return self._grads_tree

    @_grads.setter
    def _grads(self, value: Optional[Params]) -> None:
        self._grads_tree = value

    def _new_grads(self) -> Params:
        return tree_zeros_like(self._params)

    def _ensure_init(self):
        if self._params is None:
            self.reset()

    @property
    def params(self) -> Params:
        self._ensure_init()
        return self._params

    @params.setter
    def params(self, value: Params):
        self._ensure_init()
        self._params = value

    @property
    def state(self) -> State:
        self._ensure_init()
        return self._state

    @state.setter
    def state(self, value: State):
        self._state = value

    @property
    def grads(self) -> Params:
        self._ensure_init()
        return self._grads

    # ---- imperative shell ----------------------------------------------

    def forward(self, input: Activity, rng=None) -> Activity:
        """Stateful forward (reference ``AbstractModule.forward:213``).
        Wall time accumulates into ``forward_time`` (the reference's
        per-module nanosecond timing, ``AbstractModule:193-204``); the
        device is synced for an honest measurement — this shell is the
        debugging/parity path, not the fused training hot loop."""
        self._ensure_init()
        if rng is None and self.is_stochastic() and self.train_mode:
            rng = jax.random.PRNGKey(
                np.random.SeedSequence([Engine.get_seed(), next(self._rng_seq)])
                .generate_state(1)[0])
        self._last_rng = rng
        self._fwd_state_in = self._state
        t0 = time.time_ns()
        out, new_state = self._jitted()(self._params, input, self._state, rng)
        jax.block_until_ready(out)
        self.forward_time += time.time_ns() - t0
        if self.train_mode:
            self._state = new_state
        self.output = out
        return out

    def update_output(self, input: Activity) -> Activity:
        return self.forward(input)

    def backward(self, input: Activity, grad_output: Activity) -> Activity:
        """updateGradInput + accGradParameters in one VJP
        (reference ``AbstractModule.backward:231``)."""
        self._ensure_init()
        state_in = self._fwd_state_in if self._fwd_state_in is not None else self._state
        rng = self._last_rng

        def f(p, x):
            out, _ = self.apply(p, x, state_in, training=self.train_mode, rng=rng)
            return out

        t0 = time.time_ns()
        _, vjp = jax.vjp(f, self._params, input)
        pgrads, gin = vjp(grad_output)
        jax.block_until_ready(gin)
        self.backward_time += time.time_ns() - t0
        pgrads = self._scale_grads(pgrads)
        self._grads = tree_add(self._grads, pgrads)
        self.grad_input = gin
        return gin

    def update_grad_input(self, input: Activity, grad_output: Activity) -> Activity:
        """Input gradient only, no parameter-gradient accumulation."""
        self._ensure_init()
        state_in = self._fwd_state_in if self._fwd_state_in is not None else self._state
        rng = self._last_rng

        def f(x):
            out, _ = self.apply(self._params, x, state_in,
                                training=self.train_mode, rng=rng)
            return out

        _, vjp = jax.vjp(f, input)
        (gin,) = vjp(grad_output)
        self.grad_input = gin
        return gin

    def acc_grad_parameters(self, input: Activity, grad_output: Activity) -> None:
        self._ensure_init()
        state_in = self._fwd_state_in if self._fwd_state_in is not None else self._state
        rng = self._last_rng

        def f(p):
            out, _ = self.apply(p, input, state_in,
                                training=self.train_mode, rng=rng)
            return out

        _, vjp = jax.vjp(f, self._params)
        (pgrads,) = vjp(grad_output)
        self._grads = tree_add(self._grads, self._scale_grads(pgrads))

    def _scale_grads(self, pgrads):
        if self.scale_w == 1.0 and self.scale_b == 1.0:
            return pgrads
        def scale(path, g):
            leaf = path[-1].key if hasattr(path[-1], "key") else None
            s = self.scale_b if leaf == "bias" else self.scale_w
            return g * s
        return jax.tree_util.tree_map_with_path(scale, pgrads)

    def _jitted(self):
        if self._jit_apply is None:
            def fn(params, input, state, rng, training):
                return self.apply(params, input, state, training=training, rng=rng)
            # the imperative debugging/parity shell, not a fused step:
            # every hot-path compile routes through
            # utils.compile_cache.tracked_jit
            jitted = jax.jit(fn, static_argnums=(4,))  # lint: allow(untracked-jit)
            self._jit_apply = lambda p, x, s, r: jitted(p, x, s, r, self.train_mode)
        return self._jit_apply

    # ---- mode / traversal ----------------------------------------------

    def set_format(self, format: str) -> "Module":
        """Switch a spatial module's compute data format ("NCHW"/"NHWC").

        Clears this module's own jit cache; an ENCLOSING container that
        already traced this module keeps its old-format trace — call
        :meth:`clear_jit_cache` on the outermost model after re-pointing
        modules inside a live one (``nn.to_channels_last`` does)."""
        if self.layout_role != "spatial":
            raise ValueError(
                f"{type(self).__name__} has no data format (layout_role="
                f"{self.layout_role!r})")
        if format not in ("NCHW", "NHWC"):
            raise ValueError(f"unknown data format {format!r}")
        self.format = format
        self.clear_jit_cache(recursive=False)
        return self

    def clear_jit_cache(self, recursive: bool = True) -> "Module":
        """Drop cached jitted traces (forward shell + eval forward) so the
        next call re-traces — required after structural or format edits on
        an already-run model.  ``recursive`` walks the whole subtree."""
        for m in (self.modules() if recursive else (self,)):
            m._jit_apply = None
            m.__dict__.pop("_eval_jit", None)
        return self

    def is_stochastic(self) -> bool:
        """True if apply consumes rng during training (Dropout etc.)."""
        return False

    def training(self) -> "Module":
        self.train_mode = True
        self._jit_apply = None
        return self

    def evaluate(self, *args, **kwargs):
        """No args: switch to eval mode (reference ``evaluate()``).
        With (dataset, methods): run distributed evaluation."""
        if not args:
            self.train_mode = False
            self._jit_apply = None
            return self
        from bigdl_tpu.optim.evaluator import Evaluator
        return Evaluator(self).test(*args, **kwargs)

    def modules(self) -> List["Module"]:
        """All modules in the tree, depth-first, self included."""
        return [self]

    def find_modules(self, cls) -> List["Module"]:
        return [m for m in self.modules() if isinstance(m, cls)]

    def get_times(self) -> List[Tuple["Module", int, int]]:
        return [(m, m.forward_time, m.backward_time) for m in self.modules()]

    def reset_times(self) -> None:
        for m in self.modules():
            m.forward_time = 0
            m.backward_time = 0

    # ---- parameters API -------------------------------------------------

    def parameters(self) -> Tuple[Params, Params]:
        """(params pytree, grads pytree) — reference ``parameters()``."""
        self._ensure_init()
        return self._params, self._grads

    def get_parameters(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Flattened (weights, gradients) vectors
        (reference ``getParameters()`` / ``Module.flatten``, ``nn/Module.scala:80``).
        Returns concatenated copies; use :meth:`set_flat_parameters` to write back.

        The flatten runs on the HOST: after distributed training the leaf
        arrays carry heterogeneous shardings (replicated LayerNorm next to
        a Megatron-split weight), and jax 0.4.x's eager
        ``jnp.concatenate`` over mixed-sharding operands on a multi-axis
        mesh miscomputes — every element comes back scaled by the product
        of the mesh axes absent from the spec (observed 16x on a
        ('data','stage','model') mesh).  ``device_get`` + numpy sidesteps
        the partitioner entirely; the copies this API documents were
        always host-bound anyway.
        """
        self._ensure_init()
        leaves = jax.tree_util.tree_leaves(self._params)
        gleaves = jax.tree_util.tree_leaves(self._grads)
        if not leaves:
            return jnp.zeros((0,)), jnp.zeros((0,))
        w = np.concatenate([np.ravel(l) for l in jax.device_get(leaves)])
        g = np.concatenate([np.ravel(l) for l in jax.device_get(gleaves)])
        return jnp.asarray(w), jnp.asarray(g)

    def set_flat_parameters(self, flat: jnp.ndarray) -> None:
        self._ensure_init()
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape)) if l.shape else 1
            out.append(jnp.reshape(flat[off:off + n], l.shape).astype(l.dtype))
            off += n
        self._params = jax.tree_util.tree_unflatten(treedef, out)

    def n_parameters(self) -> int:
        self._ensure_init()
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self._params))

    def zero_grad_parameters(self) -> None:
        self._ensure_init()
        self._grads = tree_zeros_like(self._params)

    def update_parameters(self, learning_rate: float) -> None:
        """Vanilla in-place SGD step (reference ``updateParameters``)."""
        self._ensure_init()
        self._params = jax.tree_util.tree_map(
            lambda p, g: p - learning_rate * g, self._params, self._grads)

    def get_parameters_table(self) -> Dict[str, Params]:
        """{layer name: params} (reference ``getParametersTable()``)."""
        out = {}
        for m in self.modules():
            if not isinstance(m, Container) and m.params:
                out[m.name] = m.params
        return out

    # ---- graph-node builder --------------------------------------------

    def inputs(self, *nodes):
        """Build a graph node: ``layer.inputs(node1, node2)``
        (reference ``AbstractModule.inputs:539``)."""
        from bigdl_tpu.nn.graph import ModuleNode
        return ModuleNode(self).inputs(*nodes)

    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    # ---- clone / persistence -------------------------------------------

    def clone_module(self) -> "Module":
        """Deep copy (reference ``cloneModule:353``)."""
        return pickle.loads(pickle.dumps(self))

    def __getstate__(self):
        d = dict(self.__dict__)
        d["_jit_apply"] = None
        d.pop("_eval_jit", None)
        d["_last_rng"] = None
        d["_fwd_state_in"] = None
        d["_rng_seq"] = None
        for key in ("_params", "_state", "_grads_tree"):
            if d.get(key) is not None:
                d[key] = jax.tree_util.tree_map(np.asarray, d[key])
        return d

    def __setstate__(self, d):
        if "_grads" in d:            # pickled before the accumulator was lazy
            d = dict(d, _grads_tree=d["_grads"])
            del d["_grads"]
        self.__dict__.update(d)
        self._rng_seq = itertools.count(1)
        for key in ("_params", "_state", "_grads_tree"):
            if getattr(self, key, None) is not None:
                setattr(self, key,
                        jax.tree_util.tree_map(jnp.asarray, getattr(self, key)))

    def save(self, path: str, overwrite: bool = True) -> "Module":
        from bigdl_tpu.utils import file_io
        file_io.save(self, path, overwrite)
        return self

    # ---- prediction conveniences ---------------------------------------

    def predict(self, dataset, batch_size: int = 32, fold_bn: bool = False):
        from bigdl_tpu.optim.predictor import Predictor
        return Predictor(self, fold_bn=fold_bn).predict(dataset, batch_size)

    def predict_class(self, dataset, batch_size: int = 32,
                      fold_bn: bool = False):
        from bigdl_tpu.optim.predictor import Predictor
        return Predictor(self, fold_bn=fold_bn).predict_class(dataset,
                                                              batch_size)

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class Criterion:
    """Loss base class (reference ``nn/abstractnn/AbstractCriterion.scala:49``).

    Pure core: ``apply(input, target) -> scalar loss``.  Shell mirrors the
    reference: ``forward`` caches ``output``, ``backward`` is the VJP.
    """

    def __init__(self):
        self.output = None
        self.grad_input = None
        self.size_average = True

    def apply(self, input: Activity, target: Activity) -> jnp.ndarray:
        raise NotImplementedError(type(self).__name__)

    def forward(self, input: Activity, target: Activity):
        self.output = self.apply(input, target)
        return self.output

    def backward(self, input: Activity, target: Activity):
        _, vjp = jax.vjp(lambda x: self.apply(x, target), input)
        (self.grad_input,) = vjp(jnp.ones(()))
        return self.grad_input

    def update_grad_input(self, input, target):
        return self.backward(input, target)

    def __call__(self, input, target):
        return self.forward(input, target)


class Container(Module):
    """Module with children (reference ``nn/Container.scala:40``).

    Child params are a list aligned with ``self.children``; same for state.
    """

    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.children: List[Module] = []

    def add(self, module: Module) -> "Container":
        self.children.append(module)
        if self._params is not None:
            # adding to an ALREADY-INITIALIZED container (Torch allows
            # add() at any time): bring the new child's params in now — a
            # params list shorter than children would IndexError at the
            # next apply
            module._ensure_init()
            self._params.append(module._params)
            self._state.append(module._state)
            if self._grads_tree is not None:
                self._grads_tree.append(module._grads)
        self._jit_apply = None
        self.__dict__.pop("_eval_jit", None)
        return self

    def _init_params(self, rng) -> Params:
        return [c._init_params(_child_rng(rng, i))
                for i, c in enumerate(self.children)]

    def _init_state(self) -> State:
        return [c._init_state() for c in self.children]

    def reset(self, rng=None) -> "Module":
        super().reset(rng)
        self._adopt()
        return self

    def _adopt(self):
        """Give each child a view of its slice of params/state so individual
        child.forward() keeps working (shared, not copied — functionally
        rebuilt on sync)."""
        for i, c in enumerate(self.children):
            c._params = self._params[i]
            c._state = self._state[i]
            c._grads_tree = (None if self._grads_tree is None
                             else self._grads_tree[i])
            if isinstance(c, Container):
                c._adopt()

    def _ensure_init(self):
        if self._params is None:
            # adopt any pre-initialised children rather than clobbering them
            if any(c._params is not None for c in self.children):
                for c in self.children:
                    c._ensure_init()
                self._params = [c._params for c in self.children]
                self._state = [c._state for c in self.children]
            else:
                self.reset()

    def _new_grads(self) -> Params:
        """The children's accumulators (each allocated on this first use,
        or as far as a child has accumulated on its own), as one list."""
        if not (isinstance(self._params, list)
                and len(self._params) == len(self.children)):
            return tree_zeros_like(self._params)
        return [c._grads for c in self.children]

    def training(self) -> "Module":
        super().training()
        for c in self.children:
            c.training()
        return self

    def evaluate(self, *args, **kwargs):
        if not args:
            super().evaluate()
            for c in self.children:
                c.evaluate()
            return self
        return super().evaluate(*args, **kwargs)

    def is_stochastic(self) -> bool:
        return any(c.is_stochastic() for c in self.children)

    def modules(self) -> List[Module]:
        out: List[Module] = [self]
        for c in self.children:
            out.extend(c.modules())
        return out

    def zero_grad_parameters(self) -> None:
        super().zero_grad_parameters()
        self._adopt()

    def __getitem__(self, i: int) -> Module:
        return self.children[i]

    def __len__(self) -> int:
        return len(self.children)

    def __repr__(self):
        inner = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__}[{inner}]"


class Sequential(Container):
    """Ordered pipeline (reference ``nn/Sequential.scala:30``)."""

    def apply(self, params, input, state, training=False, rng=None):
        x = input
        new_states = []
        for i, child in enumerate(self.children):
            with child_scope(child, i):
                x, s = child.apply(params[i], x, state[i],
                                   training=training, rng=_child_rng(rng, i))
            new_states.append(s)
        return x, new_states
