"""hipBone — the paper's own benchmark as a selectable 'architecture'.

Shapes follow the paper's scaling studies: degree N=7 (3-D-threadblock
regime) and N=15 (2-D regime / peak-FOM degree), with per-rank element
boxes sized so the per-rank DOF counts bracket the paper's sweep. These
cells are EXTRA, beyond the 40 assigned LM cells.

Knob validation lives in ``PoissonConfig.__post_init__``: invalid values
and invalid *combinations* raise immediately with the offending knob named
(rather than surfacing as a deep-stack solver failure), and legal-but-
suspect combinations emit a `ConfigWarning` (see its docstring for the
list).
"""
import dataclasses
import warnings

from repro.core.coefficients import COEFFICIENTS
from repro.core.mesh import normalize_bc

__all__ = ["PoissonConfig", "ConfigWarning", "CONFIGS"]


class ConfigWarning(UserWarning):
    """A legal but suspect knob combination.

    Emitted (never raised) by ``PoissonConfig.__post_init__`` for:

    * ``precond_dtype`` narrower than ``dtype`` with
      ``cg_variant="standard"`` — a narrowed M⁻¹ is only approximately
      symmetric in the solve dtype, which the Fletcher–Reeves β assumes
      exactly; pair narrowed chains with ``cg_variant="flexible"`` (the
      Polak–Ribière β) or expect extra iterations /
      BREAKDOWN_INDEFINITE statuses near the tolerance
      (docs/SOLVERS.md, Mixed precision).
    """


@dataclasses.dataclass(frozen=True)
class PoissonConfig:
    name: str
    n_degree: int
    local_elems: tuple[int, int, int]   # elements per rank
    lam: float = 1.0
    n_iter: int = 100                   # NekBone's fixed CG iteration count
    dtype: str = "float32"
    # operator generalization knobs (core.coefficients / core.mesh):
    # coefficient selects the diffusion/screen family for
    # A = -∇·(k(x)∇) + λ(x) — "const" is the legacy constant-λ screen
    # (bit-identical builds), "smooth" a C∞ k ∈ [½, 3/2], "checker" a
    # per-element octant jump of ratio CHECKER_RHO.  bc is a boundary-
    # condition spec accepted by mesh.normalize_bc: None (legacy, no
    # essential BCs), "dirichlet"/"neumann"/"mixed", or a 6-tuple of
    # per-face tags (-x, +x, -y, +y, -z, +z).
    coefficient: str = "const"
    bc: str | tuple | None = None
    # preconditioner ladder rung: "none" (NekBone-faithful plain CG),
    # "jacobi" (assembled-diagonal scale), "chebyshev" (degree-`cheb_degree`
    # Chebyshev–Jacobi on the Lanczos-estimated [λ_min, λ_max] interval),
    # "schwarz" (overlapping element-block FDM solves, symmetric weighted
    # additive Schwarz — the robust rung for deformed/ill-conditioned
    # meshes), or "pmg" (p-multigrid V-cycle N → ⌈N/2⌉ → … → 1, the
    # production Nek5000/RS configuration).
    precond: str = "none"
    cheb_degree: int = 2                # standalone Chebyshev polynomial degree
    tol: float | None = None            # None = fixed n_iter (NekBone mode)
    # pmg knobs: per-level smoother degree (Chebyshev order of the pre/post
    # smoothing sweeps; None = per-smoother default), the smoother base
    # ("chebyshev" = Chebyshev–Jacobi, "schwarz" = Chebyshev-accelerated
    # overlapping Schwarz), the coarse-operator construction ("redisc"
    # rediscretizes, "galerkin" = exact P^T A P chained matrix-free,
    # single-device only, "galerkin_mat" = the same triple products
    # materialized at setup into per-element blocks — sharded-capable,
    # zero fine-operator work per coarse apply), and the degree of the
    # full-interval Chebyshev solve on the coarsest (N=1) ladder level.
    pmg_smooth_degree: int | None = None
    pmg_smoother: str = "chebyshev"
    pmg_coarse_op: str = "redisc"
    pmg_coarse_iters: int = 16
    # schwarz knobs: overlap width in GLL nodes (0 = FDM block Jacobi) and
    # the Chebyshev degree of the in-eigenbasis block solve (the algebraic
    # screen λI breaks pure tensor structure; higher = closer to exact
    # block inverses at ~linear extra cost per application).
    schwarz_overlap: int = 1
    schwarz_inner_degree: int = 7
    # mixed precision: compute dtype of the whole preconditioner chain
    # (None = dtype).  "float32" inside a float64 solve halves
    # preconditioner HBM/wire traffic (the production Nek5000/RS trick);
    # pair it with cg_variant="flexible" — the fp32 M⁻¹ is only
    # approximately symmetric in fp64 arithmetic.
    precond_dtype: str | None = None
    cg_variant: str = "standard"        # "standard" (FR β) | "flexible" (PR β)
    # halo-exchange routing policy for sharded solves (comms.plan):
    # "auto" times face_sweep/crystal/fused per exchange site at setup and
    # records the winners (persisted per content signature), a named
    # routing pins every site, None defers to HIPBONE_EXCHANGE (default
    # auto-less face_sweep).  Pure performance knob: iteration counts are
    # identical under every choice.  Single-device solves ignore it.
    exchange: str | None = None
    # multi-RHS serving: how many right-hand sides one solver dispatch
    # carries (core.cg.batched_cg_assembled / serving.SolverEngine slot
    # width).  1 = the classic single-column solve; the batched-solve
    # benchmark sweeps {1, 4, 16} to show setup amortization.
    batch_rhs: int = 1
    # solver guardrails (core.cg.SolveStatus): DIVERGED above
    # divergence_factor·rdotr₀ (squared-norm semantics), STAGNATED after
    # stagnation_window iterations without a stagnation_rtol relative
    # reduction of the best-seen rdotr.  None disables that detector.
    # Defaults mirror core.cg's module constants (tests pin the equality).
    divergence_factor: float | None = 1e6
    stagnation_window: int | None = 50
    stagnation_rtol: float = 0.99

    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"PoissonConfig {self.name!r}: {msg}")

        if self.n_degree < 1:
            bad(f"n_degree must be >= 1, got {self.n_degree}")
        if len(self.local_elems) != 3 or any(
            e < 1 for e in self.local_elems
        ):
            bad(
                f"local_elems must be three positive counts, "
                f"got {self.local_elems!r}"
            )
        if not self.lam > 0:
            bad(f"lam must be > 0 (screened operator is SPD), got {self.lam}")
        if self.n_iter < 1:
            bad(f"n_iter must be >= 1, got {self.n_iter}")
        if self.tol is not None and not self.tol > 0:
            bad(f"tol must be > 0 (or None for fixed-count), got {self.tol}")
        if self.dtype not in ("float32", "float64"):
            bad(f"unknown dtype {self.dtype!r}; use 'float32' or 'float64'")
        if self.coefficient not in COEFFICIENTS:
            bad(
                f"unknown coefficient {self.coefficient!r}; "
                f"choose from {COEFFICIENTS}"
            )
        try:
            normalize_bc(self.bc)
        except ValueError as e:
            bad(f"invalid bc spec: {e}")
        if self.coefficient == "checker" and any(
            e % 2 for e in self.local_elems
        ):
            warnings.warn(
                f"PoissonConfig {self.name!r}: coefficient='checker' with "
                f"odd local_elems {self.local_elems!r} — the octant jump "
                "planes at x/y/z = ½ only land on element boundaries when "
                "the per-axis *global* element counts are even; make sure "
                "the process grid restores evenness",
                ConfigWarning,
                stacklevel=3,
            )
        if self.precond not in ("none", "jacobi", "chebyshev", "schwarz", "pmg"):
            bad(f"unknown precond {self.precond!r}")
        if self.cheb_degree < 1:
            bad(f"cheb_degree must be >= 1, got {self.cheb_degree}")
        if self.pmg_smoother not in ("chebyshev", "schwarz"):
            bad(f"unknown pmg_smoother {self.pmg_smoother!r}")
        if self.pmg_coarse_op not in ("redisc", "galerkin", "galerkin_mat"):
            bad(f"unknown pmg_coarse_op {self.pmg_coarse_op!r}")
        if self.pmg_coarse_iters < 1:
            bad(f"pmg_coarse_iters must be >= 1, got {self.pmg_coarse_iters}")
        if self.precond == "pmg" and self.n_degree < 2:
            bad(
                "precond='pmg' needs n_degree >= 2 — the degree ladder "
                f"N → ⌈N/2⌉ → … → 1 has a single level at N={self.n_degree}"
            )
        if not 0 <= self.schwarz_overlap <= max(self.n_degree - 1, 0):
            bad(
                f"schwarz_overlap must be in [0, n_degree-1] = "
                f"[0, {self.n_degree - 1}], got {self.schwarz_overlap} "
                "(the overlap shell cannot exceed one element's interior)"
            )
        if self.schwarz_inner_degree < 1:
            bad(
                f"schwarz_inner_degree must be >= 1, "
                f"got {self.schwarz_inner_degree}"
            )
        if self.precond_dtype not in (None, "float32", "float64"):
            bad(f"unknown precond_dtype {self.precond_dtype!r}")
        if self.precond_dtype is not None and self.precond == "none":
            bad(
                "precond_dtype set with precond='none' — there is no "
                "preconditioner chain to cast; drop precond_dtype or pick "
                "a rung"
            )
        if self.cg_variant not in ("standard", "flexible"):
            bad(f"unknown cg_variant {self.cg_variant!r}")
        if self.exchange not in (None, "auto", "face_sweep", "crystal", "fused"):
            bad(
                f"unknown exchange {self.exchange!r}; use 'auto', "
                "'face_sweep', 'crystal', 'fused', or None "
                "(= HIPBONE_EXCHANGE env)"
            )
        if self.batch_rhs < 1:
            bad(f"batch_rhs must be >= 1, got {self.batch_rhs}")
        if self.divergence_factor is not None and not self.divergence_factor > 1:
            bad(
                f"divergence_factor must be > 1 (or None to disable), "
                f"got {self.divergence_factor}"
            )
        if self.stagnation_window is not None and self.stagnation_window < 1:
            bad(
                f"stagnation_window must be >= 1 (or None to disable), "
                f"got {self.stagnation_window}"
            )
        if not 0 < self.stagnation_rtol <= 1:
            bad(
                f"stagnation_rtol must be in (0, 1], "
                f"got {self.stagnation_rtol}"
            )
        if (
            self.precond_dtype is not None
            and self.precond_dtype != self.dtype
            and self.cg_variant == "standard"
        ):
            warnings.warn(
                f"PoissonConfig {self.name!r}: precond_dtype="
                f"{self.precond_dtype!r} with cg_variant='standard' — the "
                "narrowed M⁻¹ is only approximately symmetric in the solve "
                "dtype, which the Fletcher–Reeves β assumes exactly; use "
                "cg_variant='flexible' (see ConfigWarning)",
                ConfigWarning,
                stacklevel=3,
            )

    def dofs_per_rank(self) -> int:
        n = self.n_degree
        bx, by, bz = self.local_elems
        return bx * by * bz * n**3

    def problem_kwargs(self) -> dict:
        """This spec's operator knobs as ``core.build_problem`` kwargs.

        ``coefficient="const"`` maps to ``None`` (the legacy sentinel —
        ``build_problem`` then skips the field machinery entirely and the
        build is bit-identical to pre-coefficient configs).
        """
        return {
            "coefficient": (
                None if self.coefficient == "const" else self.coefficient
            ),
            "bc": self.bc,
        }

    def precond_kwargs(self) -> dict:
        """This spec's rung as ``core.precond.make_preconditioner`` kwargs.

        The translation the solver service (``repro.launch.serve``) and
        the setup-cache key (``core.precond.precond_signature``) share —
        only knobs relevant to the selected rung are emitted, so two
        configs differing in an inert knob map to the same setup.
        """
        if self.precond == "none":
            return {}
        kw: dict = {}
        if self.precond == "chebyshev":
            kw["degree"] = self.cheb_degree
        elif self.precond == "pmg":
            kw.update(
                pmg_smooth_degree=self.pmg_smooth_degree,
                pmg_smoother=self.pmg_smoother,
                pmg_coarse_op=self.pmg_coarse_op,
                pmg_coarse_iters=self.pmg_coarse_iters,
            )
            if self.pmg_smoother == "schwarz":
                kw.update(
                    schwarz_overlap=self.schwarz_overlap,
                    schwarz_inner_degree=self.schwarz_inner_degree,
                )
        elif self.precond == "schwarz":
            kw.update(
                schwarz_overlap=self.schwarz_overlap,
                schwarz_inner_degree=self.schwarz_inner_degree,
            )
        if self.precond_dtype is not None:
            kw["precond_dtype"] = self.precond_dtype
        return kw


CONFIGS = {
    "hipbone_n7": PoissonConfig("hipbone_n7", 7, (8, 8, 8)),      # ~176k DOF/rank
    "hipbone_n7_large": PoissonConfig("hipbone_n7_large", 7, (16, 16, 16)),
    "hipbone_n15": PoissonConfig("hipbone_n15", 15, (4, 4, 4)),   # ~216k DOF/rank
    "hipbone_n15_large": PoissonConfig("hipbone_n15_large", 15, (8, 8, 8)),
    # beyond-the-benchmark: production-style preconditioned solves to tol
    "hipbone_n7_pcg": PoissonConfig(
        "hipbone_n7_pcg", 7, (8, 8, 8), precond="chebyshev", tol=1e-6
    ),
    "hipbone_n15_pcg": PoissonConfig(
        "hipbone_n15_pcg", 15, (4, 4, 4), precond="chebyshev", tol=1e-6
    ),
    "hipbone_n7_pmg": PoissonConfig(
        "hipbone_n7_pmg", 7, (8, 8, 8), precond="pmg", tol=1e-6
    ),
    "hipbone_n15_pmg": PoissonConfig(
        "hipbone_n15_pmg", 15, (4, 4, 4), precond="pmg", tol=1e-6
    ),
    # the robust rung: overlapping-Schwarz FDM blocks, for the
    # ill-conditioned (small-λ / deformed-mesh) regime
    "hipbone_n7_schwarz": PoissonConfig(
        "hipbone_n7_schwarz", 7, (8, 8, 8), lam=0.1,
        precond="schwarz", tol=1e-8
    ),
    "hipbone_n7_pmg_schwarz": PoissonConfig(
        "hipbone_n7_pmg_schwarz", 7, (8, 8, 8), lam=0.1,
        precond="pmg", pmg_smoother="schwarz", tol=1e-8
    ),
    # the iteration-count champion for the ill-conditioned tier:
    # variationally-exact P^T A P coarse operators, materialized once at
    # setup into per-element blocks (sharded-capable, no fine-operator
    # work per coarse apply — core/galerkin.py)
    "hipbone_n7_pmg_galerkin": PoissonConfig(
        "hipbone_n7_pmg_galerkin", 7, (8, 8, 8), lam=0.1,
        precond="pmg", pmg_coarse_op="galerkin_mat", tol=1e-8
    ),
    "hipbone_n7_pmg_galerkin_fp32": PoissonConfig(
        "hipbone_n7_pmg_galerkin_fp32", 7, (8, 8, 8), lam=0.1,
        precond="pmg", pmg_coarse_op="galerkin_mat", tol=1e-8,
        dtype="float64", precond_dtype="float32", cg_variant="flexible"
    ),
    # mixed precision: fp64 outer PCG, fp32 preconditioner chain (halved
    # preconditioner HBM streams and halo wire payloads), flexible β
    "hipbone_n7_pmg_fp32": PoissonConfig(
        "hipbone_n7_pmg_fp32", 7, (8, 8, 8), lam=0.1,
        precond="pmg", tol=1e-8, dtype="float64",
        precond_dtype="float32", cg_variant="flexible"
    ),
    "hipbone_n7_schwarz_fp32": PoissonConfig(
        "hipbone_n7_schwarz_fp32", 7, (8, 8, 8), lam=0.1,
        precond="schwarz", tol=1e-8, dtype="float64",
        precond_dtype="float32", cg_variant="flexible"
    ),
    # variable-coefficient tier: A = -∇·(k(x)∇) + λ(x) with mixed
    # Dirichlet/Neumann faces, solved by the iteration-count champion
    # rung (coefficients fold into the g/w streams at setup — same
    # kernels, same FLOP count per apply; docs/SOLVERS.md)
    "hipbone_n7_smooth_mixed": PoissonConfig(
        "hipbone_n7_smooth_mixed", 7, (8, 8, 8), lam=0.1,
        coefficient="smooth", bc="mixed",
        precond="pmg", pmg_coarse_op="galerkin_mat", tol=1e-8
    ),
    "hipbone_n7_checker": PoissonConfig(
        "hipbone_n7_checker", 7, (8, 8, 8), lam=0.1,
        coefficient="checker", bc="dirichlet",
        precond="pmg", pmg_coarse_op="galerkin_mat", tol=1e-8
    ),
    # the serving shape: one Chebyshev setup amortized over a 16-column
    # RHS slab per dispatch (serving.SolverEngine / batched_cg_assembled)
    "hipbone_n7_batched": PoissonConfig(
        "hipbone_n7_batched", 7, (8, 8, 8), precond="chebyshev",
        tol=1e-6, batch_rhs=16
    ),
}

REDUCED = PoissonConfig("hipbone_reduced", 3, (2, 2, 2))
