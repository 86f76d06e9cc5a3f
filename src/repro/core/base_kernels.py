"""Base kernels for vertex- and edge-label comparison.

Every base kernel is a positive-definite function kappa(x, y) on the label
set with range in (0, 1] (vertex) or [0, 1] (edge) — the paper's condition
for the generalized Laplacian to stay SPD.

Two evaluation paths (DESIGN.md §2):

* ``__call__(x, y)`` — elementwise, used by the paper-faithful on-the-fly
  XMV (VPU path on TPU).
* ``features(x)`` — an (exact or truncated) symmetric low-rank feature map
  ``phi`` with ``kappa(x, y) = sum_r phi_r(x) * phi_r(y)``, enabling the
  beyond-paper MXU "sandwich" XMV ``y = Σ_r (A⊙φ_r(E)) P (A'⊙φ_r(E'))ᵀ``.
  Returns ``None`` if the kernel admits no useful expansion.

Differentiability (DESIGN.md §7): hyperparameter gradients of the MGK
flow through an adjoint PCG solve (core/adjoint.py), which needs every
base kernel to expose its parameters explicitly:

* ``param_names()`` / ``theta()`` — the differentiable hyperparameters
  and their current values. ``theta()`` is the canonical pytree leaf
  group the gradient entry points take derivatives against.
* ``apply(x, y, theta)`` — evaluate kappa with parameter OVERRIDES taken
  from ``theta`` (a dict; values may be JAX tracers). This is what lets
  the hot-path kernels — whose parameter fields are static Python floats
  baked into the jit cache key — consume traced parameter values: the
  overrides ride along as a tiny f32 vector input (``pack_theta``).
* ``dtheta(x, y, theta)`` — ANALYTIC elementwise derivatives
  ``∂kappa/∂θ`` per parameter. The adjoint contraction
  ``λᵀ (∂A/∂θ) x`` reuses the forward XMV machinery with kappa replaced
  by ``∂kappa/∂θ`` (:class:`ParamDerivative`), so ∂A inherits A's
  sparsity structure and is never materialized.
* ``features_theta(x, theta)`` / ``dfeatures(x, theta)`` — the feature
  expansion and its parameter derivatives, for the MXU paths.

``apply``/``dtheta``/``features_theta`` follow the input dtype (unlike
``__call__``, which keeps its historical float32 cast) so the gradcheck
suite can run the whole pipeline in float64.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from repro import obs

__all__ = [
    "BaseKernel",
    "Constant",
    "KroneckerDelta",
    "SquareExponential",
    "CompactPolynomial",
    "ParamDerivative",
    "expansion_covers",
    "pack_theta",
    "unpack_theta",
]


class BaseKernel:
    """Interface for base kernels over scalar labels."""

    def __call__(self, x, y):  # pragma: no cover - interface
        raise NotImplementedError

    def feature_rank(self) -> int | None:
        """Rank of the feature expansion, or None if not available."""
        return None

    def features(self, x):
        """phi(x) with trailing rank axis R, or None."""
        return None

    # -- differentiable-hyperparameter surface (DESIGN.md §7) -----------
    def param_names(self) -> tuple[str, ...]:
        """Names of the differentiable hyperparameters, in a fixed order
        (the order of :func:`pack_theta` vectors)."""
        return ()

    def theta(self) -> dict[str, float]:
        """Current hyperparameter values as a dict pytree."""
        return {n: getattr(self, n) for n in self.param_names()}

    def _p(self, theta, name):
        """Parameter value: ``theta`` override if present, else the
        (static) dataclass field."""
        if theta is not None and name in theta:
            return theta[name]
        return getattr(self, name)

    def apply(self, x, y, theta=None):
        """kappa(x, y) with parameters overridden from ``theta`` (values
        may be tracers). Default: no parameters -> plain ``__call__``."""
        if not self.param_names():
            return self(x, y)
        raise NotImplementedError  # pragma: no cover - interface

    def dtheta(self, x, y, theta=None) -> dict:
        """Analytic elementwise ``∂kappa/∂θ`` per parameter name."""
        if not self.param_names():
            return {}
        raise NotImplementedError  # pragma: no cover - interface

    def features_theta(self, x, theta=None):
        """``features(x)`` with parameter overrides (None if no
        expansion)."""
        if theta is None or not self.param_names():
            return self.features(x)
        raise NotImplementedError  # pragma: no cover - interface

    def dfeatures(self, x, theta=None) -> dict:
        """Analytic ``∂phi/∂θ`` per parameter name, each with the same
        trailing-R shape as ``features(x)``. Only needed when the kernel
        has a feature expansion."""
        if not self.param_names():
            return {}
        raise NotImplementedError  # pragma: no cover - interface


def expansion_covers(kernel: BaseKernel, *labels) -> bool:
    """Whether every label array lies where ``kernel``'s truncated
    feature expansion is accurate (``SquareExponential``: [0, domain]);
    True for kernels without such a domain. Host-side (numpy): each
    label array still on the device is one blocking read
    (``host_syncs``)."""
    domain = getattr(kernel, "domain", None)
    if domain is None:
        return True
    import numpy as np
    return all(float(np.min(x)) >= 0.0 and float(np.max(x)) <= domain
               for x in map(obs.to_host, labels))


def pack_theta(kernel: BaseKernel, theta=None):
    """Flatten a theta dict to the [P] f32 vector the Pallas kernels take
    as a regular array input (param_names order). None if no params."""
    names = kernel.param_names()
    if not names:
        return None
    vals = [jnp.asarray(kernel._p(theta, n), jnp.float32).reshape(())
            for n in names]
    return jnp.stack(vals)


def unpack_theta(kernel: BaseKernel, vec) -> dict | None:
    """Inverse of :func:`pack_theta`: [P] vector (or a kernel-side ref
    read) back to the {name: scalar} dict ``apply`` expects."""
    if vec is None:
        return None
    names = kernel.param_names()
    return {n: vec[i] for i, n in enumerate(names)}


@dataclasses.dataclass(frozen=True)
class Constant(BaseKernel):
    """kappa(x, y) = c. The unlabeled-graph degenerate case with c = 1."""

    value: float = 1.0

    def __call__(self, x, y):
        return jnp.full(jnp.broadcast_shapes(jnp.shape(x), jnp.shape(y)),
                        self.value, dtype=jnp.result_type(x, y, jnp.float32))

    def feature_rank(self) -> int:
        return 1

    def features(self, x):
        x = jnp.asarray(x)
        return jnp.full(x.shape + (1,), math.sqrt(self.value),
                        dtype=jnp.result_type(x, jnp.float32))

    def param_names(self) -> tuple[str, ...]:
        return ("value",)

    def apply(self, x, y, theta=None):
        c = self._p(theta, "value")
        shape = jnp.broadcast_shapes(jnp.shape(x), jnp.shape(y))
        return jnp.broadcast_to(jnp.asarray(c, jnp.result_type(x, y)),
                                shape)

    def dtheta(self, x, y, theta=None) -> dict:
        shape = jnp.broadcast_shapes(jnp.shape(x), jnp.shape(y))
        return {"value": jnp.ones(shape, jnp.result_type(x, y))}

    def features_theta(self, x, theta=None):
        x = jnp.asarray(x)
        c = self._p(theta, "value")
        root = jnp.sqrt(jnp.asarray(c, jnp.result_type(x, jnp.float32)))
        return jnp.broadcast_to(root, x.shape + (1,))

    def dfeatures(self, x, theta=None) -> dict:
        phi = self.features_theta(x, theta)
        # d sqrt(c) / dc = 1 / (2 sqrt(c))
        return {"value": 0.5 / phi}


@dataclasses.dataclass(frozen=True)
class KroneckerDelta(BaseKernel):
    """kappa(x, y) = 1 if x == y else h,  0 <= h < 1.

    Labels are integer codes in ``[0, n_labels)``. Exact feature expansion of
    rank ``n_labels + 1``:
        kappa = h * 1*1 + (1-h) * sum_c onehot_c(x) onehot_c(y).
    """

    h: float = 0.5
    n_labels: int = 8

    def __call__(self, x, y):
        eq = jnp.asarray(x) == jnp.asarray(y)
        return jnp.where(eq, 1.0, self.h).astype(jnp.float32)

    def feature_rank(self) -> int:
        return self.n_labels + 1

    def features(self, x):
        x = jnp.asarray(x)
        codes = jnp.round(x).astype(jnp.int32)
        onehot = (codes[..., None] == jnp.arange(self.n_labels)).astype(
            jnp.float32)
        const = jnp.full(x.shape + (1,), math.sqrt(self.h), jnp.float32)
        return jnp.concatenate([const, math.sqrt(1.0 - self.h) * onehot],
                               axis=-1)

    def param_names(self) -> tuple[str, ...]:
        return ("h",)

    def apply(self, x, y, theta=None):
        h = self._p(theta, "h")
        eq = jnp.asarray(x) == jnp.asarray(y)
        dt = jnp.result_type(x, y, jnp.float32)
        return jnp.where(eq, jnp.asarray(1.0, dt), jnp.asarray(h, dt))

    def dtheta(self, x, y, theta=None) -> dict:
        eq = jnp.asarray(x) == jnp.asarray(y)
        dt = jnp.result_type(x, y, jnp.float32)
        return {"h": jnp.where(eq, jnp.asarray(0.0, dt),
                               jnp.asarray(1.0, dt))}

    def _onehot(self, x):
        codes = jnp.round(jnp.asarray(x)).astype(jnp.int32)
        dt = jnp.result_type(x, jnp.float32)
        return (codes[..., None] == jnp.arange(self.n_labels)).astype(dt)

    def features_theta(self, x, theta=None):
        h = jnp.asarray(self._p(theta, "h"),
                        jnp.result_type(x, jnp.float32))
        onehot = self._onehot(x)
        const = jnp.broadcast_to(jnp.sqrt(h),
                                 jnp.shape(x) + (1,)).astype(onehot.dtype)
        return jnp.concatenate([const, jnp.sqrt(1.0 - h) * onehot],
                               axis=-1)

    def dfeatures(self, x, theta=None) -> dict:
        h = jnp.asarray(self._p(theta, "h"),
                        jnp.result_type(x, jnp.float32))
        onehot = self._onehot(x)
        const = jnp.broadcast_to(0.5 / jnp.sqrt(h),
                                 jnp.shape(x) + (1,)).astype(onehot.dtype)
        return {"h": jnp.concatenate(
            [const, -0.5 / jnp.sqrt(1.0 - h) * onehot], axis=-1)}


@dataclasses.dataclass(frozen=True)
class SquareExponential(BaseKernel):
    """kappa(x, y) = exp(-alpha (x - y)^2)   (paper Appendix B, example 1).

    Feature expansion (exact in the limit): kappa depends on x - y only,
    so with u = x - c, v = y - c around the label domain's midpoint
    c = domain / 2,
        exp(-a(x-y)^2) = exp(-a u^2) exp(-a v^2) exp(2 a u v)
    and the Taylor series exp(2auv) = sum_k (2a)^k u^k v^k / k!, the rank-R
    truncation has features
        phi_k(x) = exp(-a u^2) sqrt((2a)^k / k!) u^k,  k = 0..R-1.
    Centering bounds |u v| by domain^2 / 4: for labels in [0, 1], R = 12
    stays within ~1e-5 of kappa up to alpha = 4 and ~1e-10 at alpha = 1
    (validated in tests/test_base_kernels.py).
    """

    alpha: float = 1.0
    rank: int = 12
    domain: float = 1.0   # labels in [0, domain] keep the expansion accurate

    @property
    def center(self) -> float:
        return 0.5 * self.domain

    def __call__(self, x, y):
        d = jnp.asarray(x) - jnp.asarray(y)
        return jnp.exp(-self.alpha * d * d).astype(jnp.float32)

    def feature_rank(self) -> int:
        return self.rank

    def features(self, x):
        x = jnp.asarray(x, jnp.float32) - self.center
        ks = jnp.arange(self.rank, dtype=jnp.float32)
        # log coefficients: 0.5 * (k log(2a) - log k!)
        log_coeff = 0.5 * (ks * math.log(2.0 * self.alpha)
                           - jnp.cumsum(jnp.log(jnp.maximum(ks, 1.0))))
        coeff = jnp.exp(log_coeff)
        powers = x[..., None] ** ks
        env = jnp.exp(-self.alpha * x * x)[..., None]
        return env * coeff * powers

    def param_names(self) -> tuple[str, ...]:
        return ("alpha",)

    def apply(self, x, y, theta=None):
        a = self._p(theta, "alpha")
        d = jnp.asarray(x) - jnp.asarray(y)
        return jnp.exp(-a * d * d)

    def dtheta(self, x, y, theta=None) -> dict:
        a = self._p(theta, "alpha")
        d2 = (jnp.asarray(x) - jnp.asarray(y)) ** 2
        return {"alpha": -d2 * jnp.exp(-a * d2)}

    def features_theta(self, x, theta=None):
        x = jnp.asarray(x)
        dt = jnp.result_type(x, jnp.float32)
        x = x.astype(dt) - self.center
        a = jnp.asarray(self._p(theta, "alpha"), dt)
        ks = jnp.arange(self.rank, dtype=dt)
        log_coeff = 0.5 * (ks * jnp.log(2.0 * a)
                           - jnp.cumsum(jnp.log(jnp.maximum(ks, 1.0))))
        coeff = jnp.exp(log_coeff)
        powers = x[..., None] ** ks
        env = jnp.exp(-a * x * x)[..., None]
        return env * coeff * powers

    def dfeatures(self, x, theta=None) -> dict:
        # phi_k = exp(-a u^2) sqrt((2a)^k / k!) u^k,  u = x - c
        #   => d phi_k / da = phi_k * (k / (2a) - u^2)
        x = jnp.asarray(x)
        dt = jnp.result_type(x, jnp.float32)
        a = jnp.asarray(self._p(theta, "alpha"), dt)
        phi = self.features_theta(x, theta)
        u = x.astype(dt) - self.center
        ks = jnp.arange(self.rank, dtype=dt)
        return {"alpha": phi * (ks / (2.0 * a) - (u * u)[..., None])}


@dataclasses.dataclass(frozen=True)
class CompactPolynomial(BaseKernel):
    """Degree-n compact polynomial RBF kappa(x,y) = clip(sum_i a_i (x-y)^i).

    Paper Appendix B example 2 (Wendland-type compact kernels). Default is
    the C2 Wendland kernel on [0, 1]: (1-d)^4 (4d + 1), clipped at d = 1.
    No useful symmetric low-rank expansion — elementwise path only — which
    exercises the kernels' VPU fallback.
    """

    support: float = 1.0

    def __call__(self, x, y):
        d = jnp.abs(jnp.asarray(x) - jnp.asarray(y)) / self.support
        d = jnp.minimum(d, 1.0)
        return ((1.0 - d) ** 4 * (4.0 * d + 1.0)).astype(jnp.float32)

    def param_names(self) -> tuple[str, ...]:
        return ("support",)

    def apply(self, x, y, theta=None):
        s = self._p(theta, "support")
        d = jnp.abs(jnp.asarray(x) - jnp.asarray(y)) / s
        d = jnp.minimum(d, 1.0)
        return (1.0 - d) ** 4 * (4.0 * d + 1.0)

    def dtheta(self, x, y, theta=None) -> dict:
        # kappa(d) = (1-d)^4 (4d+1),  d = |x-y|/s  (clipped at 1):
        #   d kappa / dd = -20 d (1-d)^3,  dd/ds = -d/s
        #   => d kappa / ds = 20 d^2 (1-d)^3 / s  (0 beyond the support;
        #      continuous at d = 1 where the factor (1-d)^3 vanishes)
        s = self._p(theta, "support")
        raw = jnp.abs(jnp.asarray(x) - jnp.asarray(y)) / s
        d = jnp.minimum(raw, 1.0)
        g = 20.0 * d * d * (1.0 - d) ** 3 / s
        return {"support": jnp.where(raw < 1.0, g, jnp.zeros_like(g))}


@dataclasses.dataclass(frozen=True)
class ParamDerivative(BaseKernel):
    """The elementwise derivative ``∂kappa/∂θ_name`` of a base kernel,
    itself packaged as a (non-PSD) "kernel" so the adjoint contraction
    ``λᵀ (∂A/∂θ) x`` can reuse the forward XMV machinery verbatim — the
    same Pallas kernels, the same packs, the same sparsity (DESIGN.md
    §7). Hashable (the wrapped kernel is a frozen dataclass), so it
    rides the same static-argument slots as the kernel it derives."""

    base: BaseKernel
    name: str

    def __call__(self, x, y):
        return self.base.dtheta(x, y, None)[self.name]

    def param_names(self) -> tuple[str, ...]:
        # same parameter vector as the base kernel, so pack_theta /
        # unpack_theta round-trip transparently through the XMV wrappers
        return self.base.param_names()

    def theta(self) -> dict[str, float]:
        return self.base.theta()

    def apply(self, x, y, theta=None):
        return self.base.dtheta(x, y, theta)[self.name]
