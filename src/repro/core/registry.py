"""The estimator registry: every (p, projection, estimator) scenario as data.

The paper's method — even p with dense sub-Gaussian projections — is one
point in a family.  PAPERS.md names the rest of the lineage: α-stable
projections for fractional 0 < p <= 2 with the geometric-mean estimator
(Li arXiv:0806.4422), very sparse stable projections (Li cs/0611114), and
more.  Before this module, adding any of them meant a sweep over every
layer that compared ``estimator`` against a string literal; now a scenario
is one :class:`EstimatorSpec` registered here, and every layer — engine
strip dispatch, the index fans, the planner's route table, the micro
batcher, the front door, the launch CLI — consumes the spec:

  * the *p-domain* and compatible projection families are declared on the
    spec and validated once by :func:`resolve` at the API boundary, with
    one well-worded error naming the valid domain;
  * *route capabilities* replace estimator-name special cases: the planner
    reads ``capabilities.stacked_topk`` / ``stacked_threshold`` /
    ``fused_bitwise_stable`` instead of ``estimator == "mle"`` branches
    (mle-stays-on-dispatch is now a declared ``fused_bitwise_stable=False``
    capability, not a branch);
  * the *strip function* (``spec.pairwise``) is how the engine and the
    segment fans compute a distance strip for any estimator that does not
    use the plain packed factors.

This module is the ONLY place in ``src/repro`` where the estimator names
appear as string literals (``tools/check_no_literal_estimators.py`` is the
CI guard).  Everyone else imports :data:`PLAIN` / :data:`MARGIN_MLE` /
:data:`GEOMETRIC_MEAN` / :data:`DEFAULT_ESTIMATOR` or enumerates
:func:`names` / :func:`names_for`.

Registering a new estimator::

    from repro.core import registry

    registry.register_estimator(registry.EstimatorSpec(
        name="hm",
        description="harmonic-mean estimator over stable projections",
        p_domain=registry.FRACTIONAL_P,
        projections=("stable", "stable_sparse"),
        uses_packed=False,
        pairwise=my_pairwise_strips,      # (sa, sb, cfg, *, clip) -> (n, m)
        variance=my_variance_model,        # optional
        capabilities=registry.RouteCapabilities(),  # dispatch-only
    ))

The registry is deliberately *not* an import-time side effect of the
feature modules: built-in specs are registered lazily on first lookup so
``repro.core.registry`` stays a leaf module any layer may import.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "PDomain",
    "RouteCapabilities",
    "EstimatorSpec",
    "register_estimator",
    "get",
    "resolve",
    "names",
    "names_for",
    "specs",
    "EVEN_P",
    "SKETCH_EVEN_P",
    "FRACTIONAL_P",
    "PLAIN",
    "MARGIN_MLE",
    "GEOMETRIC_MEAN",
    "DEFAULT_ESTIMATOR",
    "STACKED_PACKED",
    "STACKED_SKETCH",
]

# canonical estimator names — the only quoted estimator literals in src/repro
PLAIN = "plain"
MARGIN_MLE = "mle"
GEOMETRIC_MEAN = "gm"
DEFAULT_ESTIMATOR = PLAIN

# stacked stage-1 program families (RouteCapabilities.stacked_topk values):
# which shard_map program can serve this estimator's stacked top-k fan
STACKED_PACKED = "packed"      # packed-factor matmul strips (plain)
STACKED_SKETCH = "sketch_mle"  # raw-sketch Newton strips (margin-MLE)


@dataclasses.dataclass(frozen=True)
class PDomain:
    """Valid p values for one consumer (an estimator or a decomposition).

    Two shapes cover everything the stack serves today:

      * ``even_min=q`` — even integers p >= q (the paper's decomposition);
      * ``lo``/``hi``  — the half-open interval lo < p <= hi (α-stable
        projections, fractional p).
    """

    even_min: Optional[int] = None
    lo: Optional[float] = None
    hi: Optional[float] = None

    def __post_init__(self):
        if (self.even_min is None) == (self.lo is None or self.hi is None):
            raise ValueError(
                "PDomain needs either even_min or a (lo, hi] interval")

    @property
    def describe(self) -> str:
        if self.even_min is not None:
            return f"even p >= {self.even_min}"
        return f"{self.lo} < p <= {self.hi}"

    def contains(self, p) -> bool:
        if self.even_min is not None:
            return (float(p).is_integer() and int(p) >= self.even_min
                    and int(p) % 2 == 0)
        return self.lo < float(p) <= self.hi

    def check(self, p, *, what: str) -> None:
        """Raise the stack's single, well-worded p-domain error."""
        if not self.contains(p):
            raise ValueError(f"{what} requires {self.describe}, got p={p}")


# the shared p-domains (consumers import these instead of re-asserting)
EVEN_P = PDomain(even_min=2)          # the exact decomposition identities
SKETCH_EVEN_P = PDomain(even_min=4)   # the paper's sketch (p-1 >= 3 orders)
FRACTIONAL_P = PDomain(lo=0.0, hi=2.0)  # α-stable projections, α = p


@dataclasses.dataclass(frozen=True)
class RouteCapabilities:
    """What serving routes an estimator's strips can legally ride.

    Attributes:
      stacked_topk: which stacked shard_map top-k program serves this
        estimator (:data:`STACKED_PACKED` / :data:`STACKED_SKETCH`), or
        ``None`` when no stacked program exists — the planner then never
        routes its top-k queries to the stacked fan.
      stacked_threshold: a stacked threshold program exists.
      fused_bitwise_stable: the estimator's strips are bitwise invariant
        under the stacked fan's re-tiling/fusion contexts.  When False the
        planner keeps the estimator on the exact dispatch fan unless the
        caller opts into an ``ApproxContract`` (the tolerance-gated route).
    """

    stacked_topk: Optional[str] = None
    stacked_threshold: bool = False
    fused_bitwise_stable: bool = False


@dataclasses.dataclass(frozen=True)
class EstimatorSpec:
    """One estimator scenario, declared as data.

    Attributes:
      name: the public estimator name (the ``estimator=`` string).
      description: one line for docs / CLI help.
      p_domain: valid p values (:class:`PDomain`).
      projections: projection families the estimator's sketches use.
      uses_packed: the query side packs ``(A, nq)`` factors and strips run
        as one packed matmul (the plain estimator); False = strips call
        ``pairwise`` on raw sketches.
      pairwise: ``(sa, sb, cfg, *, clip=True) -> (n, m)`` strip estimates
        for raw-sketch estimators (also the dense reference for tests).  It
        must be traceable (jnp code, ``cfg`` static): the segment fan calls
        it inside one compiled loop over a segment's strips.
      variance: optional per-pair variance model
        ``(x, y, p, k) -> Var[d_hat]`` (the Lemma-4-style gates).
      capabilities: :class:`RouteCapabilities` the planner consumes.
    """

    name: str
    description: str
    p_domain: PDomain
    projections: Tuple[str, ...]
    uses_packed: bool
    pairwise: Callable
    variance: Optional[Callable] = None
    capabilities: RouteCapabilities = RouteCapabilities()

    def compatible_with(self, cfg) -> bool:
        """Does this spec serve ``cfg``'s (p, projection family)?"""
        return (self.p_domain.contains(cfg.p)
                and cfg.projection.family in self.projections)


_LOCK = threading.Lock()
_SPECS: Dict[str, EstimatorSpec] = {}
_BUILTINS_REGISTERED = False

# the dense sub-Gaussian families the paper's even-p estimators accept
_SUBGAUSSIAN = ("normal", "uniform", "threepoint")
# the α-stable families fractional-p estimators accept
_STABLE = ("stable", "stable_sparse")


def register_estimator(spec: EstimatorSpec, *, overwrite: bool = False) -> EstimatorSpec:
    """Add ``spec`` to the process-global registry (thread-safe).

    Re-registering an existing name raises unless ``overwrite=True`` — a
    silent replacement would change serving behavior process-wide.
    """
    if not isinstance(spec, EstimatorSpec):
        raise TypeError(f"expected an EstimatorSpec, got {type(spec).__name__}")
    _ensure_builtins()
    with _LOCK:
        if spec.name in _SPECS and not overwrite:
            raise ValueError(
                f"estimator {spec.name!r} is already registered "
                f"(pass overwrite=True to replace it)")
        _SPECS[spec.name] = spec
    return spec


def get(name: str) -> EstimatorSpec:
    """Spec for ``name``; unknown names raise a ValueError listing the
    registered estimators."""
    _ensure_builtins()
    with _LOCK:
        spec = _SPECS.get(name)
    if spec is None:
        known = ", ".join(repr(n) for n in names())
        raise ValueError(f"unknown estimator {name!r} (registered: {known})")
    return spec


def resolve(name: str, p=None, projection: Optional[str] = None) -> EstimatorSpec:
    """The one validation gate: name -> spec, with (p, projection) checked
    against the spec's declared domain.

    Layers call this once at their API boundary and pass the spec down;
    nothing downstream re-validates.
    """
    spec = get(name)
    if p is not None:
        spec.p_domain.check(p, what=f"estimator {spec.name!r}")
    if projection is not None and projection not in spec.projections:
        fams = ", ".join(repr(f) for f in spec.projections)
        raise ValueError(
            f"estimator {spec.name!r} requires a projection family in "
            f"({fams}), got {projection!r}")
    return spec


def names() -> Tuple[str, ...]:
    """Registered estimator names, in registration order (built-ins first)."""
    _ensure_builtins()
    with _LOCK:
        return tuple(_SPECS)


def specs() -> Tuple[EstimatorSpec, ...]:
    _ensure_builtins()
    with _LOCK:
        return tuple(_SPECS.values())


def names_for(cfg) -> Tuple[str, ...]:
    """Estimator names whose declared domain serves ``cfg`` — what
    ``stats()`` / CLIs enumerate instead of hard-coding the name list."""
    return tuple(s.name for s in specs() if s.compatible_with(cfg))


def _ensure_builtins() -> None:
    """Register the built-in specs lazily (idempotent, thread-safe).

    Lazy so this module stays a leaf import: the feature modules the specs
    point at (pairwise, estimators, stable) themselves import core modules
    that may import the registry.
    """
    global _BUILTINS_REGISTERED
    if _BUILTINS_REGISTERED:
        return
    with _LOCK:
        if _BUILTINS_REGISTERED:
            return
        from .pairwise import pairwise_distances, pairwise_margin_mle
        from .stable import pairwise_geometric_mean, variance_geometric_mean
        from .variance import variance_margin_mle, variance_plain

        _SPECS[PLAIN] = EstimatorSpec(
            name=PLAIN,
            description="unbiased packed-matmul estimator (paper §2.1)",
            p_domain=SKETCH_EVEN_P,
            projections=_SUBGAUSSIAN,
            uses_packed=True,
            pairwise=pairwise_distances,
            variance=variance_plain,
            capabilities=RouteCapabilities(
                stacked_topk=STACKED_PACKED,
                stacked_threshold=True,
                fused_bitwise_stable=True,
            ),
        )
        _SPECS[MARGIN_MLE] = EstimatorSpec(
            name=MARGIN_MLE,
            description="margin-regularized MLE, Newton per strip (Lemma 4)",
            p_domain=SKETCH_EVEN_P,
            projections=_SUBGAUSSIAN,
            uses_packed=False,
            pairwise=pairwise_margin_mle,
            variance=variance_margin_mle,
            capabilities=RouteCapabilities(
                stacked_topk=STACKED_SKETCH,
                stacked_threshold=False,
                # Newton strips are NOT bitwise stable under the stacked
                # fan's fusion contexts: dispatch unless an ApproxContract
                # opts the query into the tolerance-gated stacked route
                fused_bitwise_stable=False,
            ),
        )
        _SPECS[GEOMETRIC_MEAN] = EstimatorSpec(
            name=GEOMETRIC_MEAN,
            description="geometric-mean estimator over α-stable projections "
                        "for fractional 0 < p <= 2 (Li arXiv:0806.4422)",
            p_domain=FRACTIONAL_P,
            projections=_STABLE,
            uses_packed=False,
            pairwise=pairwise_geometric_mean,
            variance=variance_geometric_mean,
            # no stacked programs yet: every query rides the dispatch fan,
            # which is already bit-identical across hosts/replicas
            capabilities=RouteCapabilities(),
        )
        _BUILTINS_REGISTERED = True
