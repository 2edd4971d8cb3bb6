"""Backend dispatch for all banded algebra in the GP core.

Every banded op the core performs — matvec, solve, logdet, band x band
matmul, KP Gram assembly — routes through this module and is served by one
of two backends:

  * ``"jax"``    — the pure-jax ``lax.scan`` reference implementations in
                   ``repro.core.banded`` (compiled by XLA, CPU/GPU/TPU),
                   and for long unpivoted symmetric-band solves the
                   log-depth block cyclic reduction of ``cr_jax.py``.
  * ``"pallas"`` — the Pallas TPU kernels in this package. Whether they run
                   compiled or in the interpreter is decided in one place,
                   ``interpret_kernels()``: interpreted exactly when no TPU
                   is attached, so the same code path is testable on a CPU,
                   and never interpreted on a TPU.
  * ``"auto"``   — resolved by the **backend rule** below.

Selection precedence (first wins):
  1. an explicit ``"jax"``/``"pallas"`` ``backend=`` argument (threaded from
     ``GPConfig.backend`` / ``SolveConfig.backend``),
  2. the process-wide default set by ``set_backend`` / ``use_backend`` or the
     ``REPRO_BACKEND`` environment variable (consulted when the argument is
     ``None`` or ``"auto"`` — the config default — so the env var reaches
     every routed op in the GP core),
  3. the backend rule, a static function of what the code can observe — the
     platform and the compute dtype:

       * 64-bit operands resolve to ``"jax"`` on every platform: Mosaic, the
         Pallas TPU compiler, has no 64-bit types;
       * otherwise ``"pallas"`` on a TPU once every kernel the backend
         routes to lowers there (``TPU_UNLOWERED`` is empty), and ``"jax"``
         until then and everywhere else.

     An explicit ``"pallas"`` with 64-bit operands on a TPU raises instead
     of falling back to the interpreter.

``fit()`` applies the rule once, with the data's dtype, and bakes the result
into the GP config, so the choice is a trace-time static and jitted GP entry
points specialize per backend (``GPConfig`` is a static/meta field).

``TPU_UNLOWERED`` names the kernels the TPU compiler still refuses (pinned
by the compile rehearsals in ``tests/test_tpu_lowering.py``). On a TPU an
explicit ``"pallas"`` op that needs one of them raises, never substituting
its jax-scan form, and ``"auto"`` fusion steps down past them to ``"off"``
(an explicit fused mode that needs one raises). This is a named static
exclusion, not a caught compile error.

The pallas solve/logdet path additionally selects between two kernel
algorithms (``REPRO_SOLVE_ALG`` env / ``set_solve_alg`` / the per-op
``alg=`` argument threaded from ``GPConfig.solve_alg``/``SolveConfig.alg``):

  * ``"cr"`` — block cyclic reduction (``block_cr.py``): fully vectorized
    ceil(log2(n/w)) elimination levels, batched into the kernel grid, with a
    block partial-pivot mode. Requires ``lo == hi`` (every KP system has it).
  * ``"lu"`` — the sequential row-recurrence LU kernel (``banded_lu.py``).
  * ``"auto"`` (default) — ``"cr"`` whenever ``lo == hi >= 1``, else ``"lu"``
    (diagonal bands stay on the already-loop-free LU path).

``pivot=True`` routes to the pivoted block-CR kernel when the resolved
algorithm is ``"cr"``; only the asymmetric-bandwidth (or forced-``"lu"``)
pivoted case still falls back to the jax gbsv-style scan.

The same ``"cr"``/``"lu"`` choice applies to the jax backend's unpivoted
solves: a band with ``lo == hi >= 1`` and at least ``CR_MIN_BLOCK_ROWS``
block rows (``n / w``) solves by the pure-jax block cyclic reduction
(``cr_jax.block_cr_solve_jax``) when the alg resolves to ``"cr"``, in
ceil(log2(n/w)) vectorized levels each way instead of n sequential scan
steps. Shorter systems, ``pivot=True``, ``lo != hi``, diagonal bands and
``"lu"`` keep the scan LU; so do the logdet and the RGF band inverse. The
``solve.cr`` / ``solve.scan`` counters of ``repro.obs`` count, at trace
time, the solves that took each route.

Batched operands (the GP's stacked per-dimension factors, leading dims)
are flattened and folded into the kernel **grid** for every pallas kernel
(``_flatten_batch`` -> one ``pallas_call``); no op unrolls its batch at
trace time any more.

Capacity padding: every dispatched op (and every pallas kernel wrapper)
accepts a traced ``n_active``. Operands are canonicalized first
(``masking.canonical_band`` / ``masking.mask_rows``): padding rows become
decoupled identity rows / zeros, so the padded system is exactly
``blockdiag(M_active, I)`` — solves, matvecs, matmuls and logdets are exact
on the active prefix, no-ops on the tail, under ONE static shape per
capacity. This is what makes the streaming insert/evict path recompile-free
(see ``repro.streaming``).

Orthogonally to the per-op backends, the backfitting solvers can fuse one
*whole* iteration — permutation gathers, matvecs, block-CR solve and the
cross-dimension coupling — into a single ``pallas_call``
(``kernels/fused_sweep.py``). The ``REPRO_FUSED`` env / ``set_fused`` /
``SolveConfig.fused`` / ``GPConfig.fused`` switch controls it:

  * ``"auto"`` (default) — fuse when the resolved backend is pallas, every
    factor has a symmetric bandwidth (lo == hi — true for every KP system),
    the preconditioner is not kmg (its V-cycle is a host-level construction
    neither fused pcg kernel can apply), and the estimated VMEM footprint
    fits (vs ``REPRO_FUSED_VMEM_CAP``): preferring the *whole-solve* kernel
    (below), then the per-iteration kernel, then the unfused dispatch path.
  * ``"whole"`` — require the whole-solve mega-kernel
    (``kernels/mega_solve.py``): the convergence loop itself runs on-chip,
    so the entire ``solve_mhat`` is ONE ``pallas_call``. Raises wherever
    ``"on"`` would.
  * ``"on"`` — require per-iteration fusion (raises if the
    backend/bandwidths/preconditioner can't).
  * ``"off"`` — never fuse.
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp

from .band_matmul import band_matmul_pallas
from .banded_lu import banded_logdet_pallas, banded_solve_pallas
from .banded_matvec import banded_matvec_pallas
from .block_cr import block_cr_logdet_pallas, block_cr_solve_pallas
from .cr_jax import block_cr_solve_jax
from .fused_sweep import fused_vmem_bytes
from .kp_gram import kp_gram_pallas
from .. import obs
from ..masking import canonical_band, mask_rows

__all__ = [
    "BACKENDS", "SOLVE_ALGS", "FUSED_MODES", "PRECOND_MODES", "on_tpu",
    "get_backend", "set_backend", "use_backend", "resolve_backend",
    "get_solve_alg", "set_solve_alg", "use_solve_alg", "resolve_solve_alg",
    "get_fused", "set_fused", "use_fused", "resolve_fused", "get_precond",
    "set_precond", "use_precond", "resolve_precond", "get_gband", "set_gband",
    "use_gband", "resolve_gband", "banded_matvec", "banded_solve",
    "banded_logdet", "band_band_matmul", "kp_gram", "GBAND_MODES",
    "HEALTH_MODES", "get_health", "set_health", "use_health",
    "resolve_health",
]

BACKENDS = ("auto", "jax", "pallas")
ENV_VAR = "REPRO_BACKEND"

SOLVE_ALGS = ("auto", "lu", "cr")
ENV_SOLVE_ALG = "REPRO_SOLVE_ALG"

FUSED_MODES = ("auto", "on", "whole", "off")
ENV_FUSED = "REPRO_FUSED"

PRECOND_MODES = ("auto", "none", "kmg")
ENV_PRECOND = "REPRO_PRECOND"

GBAND_MODES = ("auto", "windowed", "full")
ENV_GBAND = "REPRO_GBAND"

HEALTH_MODES = ("auto", "on", "off")
ENV_HEALTH = "REPRO_HEALTH"

# "auto" precond gate: enable the kernel-multigrid V-cycle at q == 0 once
# the system is large enough that the coarse correction pays for its extra
# matvecs (~2-3x per iteration vs a 2-4x iteration-count cut, so the
# crossover sits around 4k points); q >= 1 declines — assembling
# Khat^{-1} = Phi^{-1} A at q >= 1 amplifies f64 cancellation to ~1e13
# spectral range and the coarse correction stops resembling the fine
# operator (see kernels/README.md)
KMG_AUTO_MIN_N = 4096

# jax-backend unpivoted solves of a band with lo == hi >= 1 run the
# log-depth block cyclic reduction of ``cr_jax`` (when the solve alg
# resolves to "cr") from this many block rows (n / w) up, and the
# sequential scan LU below it. On a TPU v5e (float64, D = 10, B = 32; the
# table in PERF.md) CR was faster from 16 block rows, but by 13-43 us a
# solve (1.2-1.6x) below 64, for about twice the scan's code; from 64 up it
# is 2.5-13x. 64 also keeps the tiny systems of the CPU tests (capacity-16
# fleets and padding checks) on the scan.
CR_MIN_BLOCK_ROWS = 64

def _env_mode(var: str, valid: tuple[str, ...]) -> str:
    """Read a mode env var, failing *at import* on an invalid value.

    A typo'd ``REPRO_*`` setting used to survive module load and only blow
    up deep inside a trace (or worse, silently select a fallback); raising
    here surfaces the mistake immediately, with the valid options listed.
    """
    val = os.environ.get(var, "auto")
    if val not in valid:
        raise ValueError(
            f"invalid {var}={val!r}; expected one of {valid}")
    return val


_backend = _env_mode(ENV_VAR, BACKENDS)
_solve_alg = _env_mode(ENV_SOLVE_ALG, SOLVE_ALGS)
_fused = _env_mode(ENV_FUSED, FUSED_MODES)
_precond = _env_mode(ENV_PRECOND, PRECOND_MODES)
_gband = _env_mode(ENV_GBAND, GBAND_MODES)
_health = _env_mode(ENV_HEALTH, HEALTH_MODES)


# Pallas kernels that do not lower for the TPU yet (see ROADMAP.md queue 1):
#   * fused_sweep / mega_solve — in-kernel sort/rank permutation gathers;
#   * rgf — per-step dynamic block indexing and a pivoted scan-LU per block;
#   * block_cr — (nb, w, w) block layout with in-kernel scatters/reshapes;
#   * banded_lu — 1-D row updates by scatter in the row recurrence.
# On a TPU no resolver routes to these; every other kernel compiles (f32).
TPU_UNLOWERED = frozenset(
    {"fused_sweep", "mega_solve", "rgf", "block_cr", "banded_lu"})


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_kernels() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly off-TPU.

    The one place that decides; every kernel wrapper takes ``interpret``
    without a default, and on a TPU this never returns True.
    """
    return not on_tpu()


def kernel_lowers(name: str) -> bool:
    """False when ``name`` is a kernel the attached TPU cannot compile yet
    (``TPU_UNLOWERED``); always True off-TPU, where kernels interpret."""
    return not (on_tpu() and name in TPU_UNLOWERED)


def require_lowered(name: str) -> None:
    """Raise when the pallas backend would run kernel ``name`` on a TPU
    that cannot compile it (``TPU_UNLOWERED``)."""
    if not kernel_lowers(name):
        raise ValueError(
            f"backend='pallas' needs the {name} kernel, which does not lower "
            "for the TPU yet (kernels.ops.TPU_UNLOWERED); use backend='auto' "
            "or 'jax' (XLA)")


def get_backend() -> str:
    """Current process-wide default backend name (may be "auto")."""
    return _backend


def set_backend(name: str) -> None:
    """Set the process-wide default backend ("auto" | "jax" | "pallas")."""
    global _backend
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    _backend = name


@contextlib.contextmanager
def use_backend(name: str):
    """Temporarily override the default backend (trace-time scope)."""
    prev = _backend
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def resolve_backend(backend: str | None = None, dtype=None) -> str:
    """Resolve an op-level override (or the global default) to jax|pallas.

    An explicit "jax"/"pallas" wins; "auto" (the GPConfig/SolveConfig
    default) and None defer to the process default (set_backend /
    REPRO_BACKEND); an "auto" process default resolves by the backend rule
    (module docstring): 64-bit ``dtype`` -> "jax" on every platform, else
    "pallas" on a TPU whose pallas kernels all lower and "jax" otherwise.
    ``dtype`` None means "not known here" (the per-op calls on an
    already-baked config). A "pallas" that would compile 64-bit operands on
    a TPU raises.
    """
    b = backend if backend is not None else _backend
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; expected one of {BACKENDS}")
    if b == "auto":
        b = _backend  # config-level "auto" defers to the process default
        if b not in BACKENDS:
            # process default comes from REPRO_BACKEND unvalidated; a typo'd
            # env value must raise here, not silently select a backend
            raise ValueError(
                f"unknown backend {b!r} (from {ENV_VAR} or set_backend); "
                f"expected one of {BACKENDS}")
    wide = dtype is not None and jnp.dtype(dtype).itemsize == 8
    if b == "auto":
        return ("pallas" if on_tpu() and not wide and not TPU_UNLOWERED
                else "jax")
    if b == "pallas" and wide and on_tpu():
        raise ValueError(
            f"backend='pallas' cannot run {jnp.dtype(dtype).name} operands "
            "on a TPU: Mosaic has no 64-bit types. Use backend='auto' or "
            "'jax' (XLA), or compute in float32")
    return b


def get_solve_alg() -> str:
    """Current process-wide pallas solve algorithm (may be "auto")."""
    return _solve_alg


def set_solve_alg(name: str) -> None:
    """Set the process-wide pallas solve algorithm ("auto" | "lu" | "cr")."""
    global _solve_alg
    if name not in SOLVE_ALGS:
        raise ValueError(
            f"unknown solve alg {name!r}; expected one of {SOLVE_ALGS}")
    _solve_alg = name


@contextlib.contextmanager
def use_solve_alg(name: str):
    """Temporarily override the pallas solve algorithm (trace-time scope)."""
    prev = _solve_alg
    set_solve_alg(name)
    try:
        yield
    finally:
        set_solve_alg(prev)


def resolve_solve_alg(alg: str | None, lo: int, hi: int) -> str:
    """Resolve the pallas solve/logdet kernel algorithm to "lu" | "cr".

    An explicit "lu"/"cr" ``alg`` wins; "auto" (the GPConfig/SolveConfig
    default) and None defer to the process default (set_solve_alg /
    REPRO_SOLVE_ALG). "auto" selects block cyclic reduction whenever the
    bandwidth is symmetric (``lo == hi`` — true for every KP system the GP
    core builds) and the sequential LU kernel otherwise. Forcing "cr" on an
    asymmetric band is an error (CR's block-tridiagonal view needs lo == hi).
    """
    explicit = alg is not None and alg != "auto"
    a = alg if alg is not None else _solve_alg
    if a not in SOLVE_ALGS:
        raise ValueError(
            f"unknown solve alg {a!r} (from {ENV_SOLVE_ALG} or "
            f"set_solve_alg); expected one of {SOLVE_ALGS}")
    if a == "auto":
        a = _solve_alg
        if a not in SOLVE_ALGS:
            raise ValueError(
                f"unknown solve alg {a!r} (from {ENV_SOLVE_ALG} or "
                f"set_solve_alg); expected one of {SOLVE_ALGS}")
    if a == "auto":
        return "cr" if lo == hi and lo > 0 else "lu"
    if a == "cr" and lo == hi == 0:
        return "lu"  # diagonal: the LU kernel is already loop-free there
    if a == "cr" and lo != hi:
        if explicit:
            raise ValueError(
                f"solve alg 'cr' requires a symmetric bandwidth (lo == hi); "
                f"got lo={lo}, hi={hi}")
        return "lu"  # process-default "cr" means prefer-CR-where-applicable
    return a


def get_fused() -> str:
    """Current process-wide fused-sweep mode (may be "auto")."""
    return _fused


def set_fused(name: str) -> None:
    """Set the process-wide fused mode ("auto" | "on" | "whole" | "off")."""
    global _fused
    if name not in FUSED_MODES:
        raise ValueError(
            f"unknown fused mode {name!r}; expected one of {FUSED_MODES}")
    _fused = name


@contextlib.contextmanager
def use_fused(name: str):
    """Temporarily override the fused-sweep mode (trace-time scope)."""
    prev = _fused
    set_fused(name)
    try:
        yield
    finally:
        set_fused(prev)


def resolve_fused(fused: str | None, backend: str | None, *, widths,
                  n: int = 0, D: int = 1, B: int = 1, itemsize: int = 8,
                  method: str = "pcg", cr_ok: bool = True,
                  precond: str = "none") -> str:
    """Decide how a backfitting solve fuses; returns "whole"|"iter"|"off".

    ``widths``: the (lo, hi) pairs of every band the sweep touches. An
    explicit mode wins (``"on"``/``"whole"`` raise if fusion is impossible:
    jax backend, asymmetric bandwidths, a solve-alg override that forbids
    block CR — the only solve the fused kernels implement; callers pass that
    as ``cr_ok`` — or ``precond='kmg'``, whose host-level V-cycle neither
    fused pcg kernel can apply); ``"auto"``/None defer to the process
    default (``set_fused`` / ``REPRO_FUSED``), and a final "auto" requires
    the pallas backend, symmetric bands, CR and ``precond != 'kmg'``, then
    takes the whole-solve kernel when ``mega_solve.mega_vmem_bytes`` fits
    under ``fused_sweep.VMEM_CAP_BYTES`` (env ``REPRO_FUSED_VMEM_CAP``),
    falls back to the per-iteration kernel when ``fused_vmem_bytes`` fits,
    and otherwise runs unfused. ``"on"`` pins the per-iteration kernel.
    """
    from . import fused_sweep, mega_solve

    f = fused if fused is not None else _fused
    if f not in FUSED_MODES:
        raise ValueError(
            f"unknown fused mode {f!r}; expected one of {FUSED_MODES}")
    if f == "auto":
        f = _fused
        if f not in FUSED_MODES:
            raise ValueError(
                f"unknown fused mode {f!r} (from {ENV_FUSED} or set_fused); "
                f"expected one of {FUSED_MODES}")
    if f == "off":
        return "off"
    be = resolve_backend(backend)
    symmetric = all(lo == hi for lo, hi in widths)
    kernel = "mega_solve" if f == "whole" else "fused_sweep"
    if f in ("on", "whole"):
        if not kernel_lowers(kernel):
            raise ValueError(
                f"fused={f!r} needs the {kernel} kernel, which does not "
                "lower for the TPU yet (kernels.ops.TPU_UNLOWERED)")
        if be != "pallas":
            raise ValueError(
                f"fused={f!r} requires the pallas backend (got "
                f"backend={be!r}); the fused sweep is a Pallas kernel")
        if not symmetric:
            raise ValueError(
                f"fused={f!r} requires symmetric bandwidths (lo == hi) on "
                f"every factor; got {tuple(widths)}")
        if not cr_ok:
            raise ValueError(
                f"fused={f!r} conflicts with solve alg 'lu': the fused sweep "
                "solves via block cyclic reduction only")
        if precond == "kmg":
            raise ValueError(
                f"fused={f!r} is incompatible with precond='kmg': the "
                "V-cycle is a host-level construction and the fused pcg "
                "kernels hard-code the block preconditioner; use "
                "precond='none' or drop the fused override")
        return "whole" if f == "whole" else "iter"
    if be != "pallas" or not symmetric or not cr_ok or precond == "kmg":
        return "off"
    ws = [lo for lo, _ in widths]
    if kernel_lowers("mega_solve") and mega_solve.mega_vmem_bytes(
            n, D, B, ws, itemsize, method=method) <= fused_sweep.VMEM_CAP_BYTES:
        return "whole"
    if kernel_lowers("fused_sweep") and fused_vmem_bytes(
            n, D, B, ws, itemsize, method=method) <= fused_sweep.VMEM_CAP_BYTES:
        return "iter"
    return "off"


def get_precond() -> str:
    """Current process-wide preconditioner mode (may be "auto")."""
    return _precond


def set_precond(name: str) -> None:
    """Set the process-wide preconditioner mode ("auto" | "none" | "kmg")."""
    global _precond
    if name not in PRECOND_MODES:
        raise ValueError(
            f"unknown precond mode {name!r}; expected one of {PRECOND_MODES}")
    _precond = name


@contextlib.contextmanager
def use_precond(name: str):
    """Temporarily override the preconditioner mode (trace-time scope)."""
    prev = _precond
    set_precond(name)
    try:
        yield
    finally:
        set_precond(prev)


def resolve_precond(precond: str | None, *, q: int, n: int) -> str:
    """Resolve the backfitting PCG preconditioner to "none" | "kmg".

    An explicit "none"/"kmg" wins; "auto" (the GPConfig/SolveConfig
    default) and None defer to the process default (``set_precond`` /
    ``REPRO_PRECOND``). A final "auto" enables the kernel-multigrid
    V-cycle exactly when ``q == 0`` and ``n >= KMG_AUTO_MIN_N`` (both
    static): below that the coarse correction's extra work outweighs the
    iteration cut, and at q >= 1 the f64 cancellation in assembling
    Khat^{-1} makes the coarse operator unreliable (forcing "kmg" there
    stays SPD-safe via the clamped deflation, just not profitable).
    ``fit()`` calls this once and bakes the result into the GP config, so
    jit caches key on the resolved choice.
    """
    p = precond if precond is not None else _precond
    if p not in PRECOND_MODES:
        raise ValueError(
            f"unknown precond mode {p!r}; expected one of {PRECOND_MODES}")
    if p == "auto":
        p = _precond
        if p not in PRECOND_MODES:
            raise ValueError(
                f"unknown precond mode {p!r} (from {ENV_PRECOND} or "
                f"set_precond); expected one of {PRECOND_MODES}")
    if p == "auto":
        return "kmg" if q == 0 and n >= KMG_AUTO_MIN_N else "none"
    return p


def get_gband() -> str:
    """Current process-wide Gband maintenance mode (may be "auto")."""
    return _gband


def set_gband(name: str) -> None:
    """Set the process-wide Gband mode ("auto" | "windowed" | "full")."""
    global _gband
    if name not in GBAND_MODES:
        raise ValueError(
            f"unknown gband mode {name!r}; expected one of {GBAND_MODES}")
    _gband = name


@contextlib.contextmanager
def use_gband(name: str):
    """Temporarily override the Gband maintenance mode (trace-time scope)."""
    prev = _gband
    set_gband(name)
    try:
        yield
    finally:
        set_gband(prev)


def resolve_gband(gband: str | None = None) -> str:
    """Resolve the streaming Gband maintenance mode to "windowed" | "full".

    "windowed" keeps the cached variance band ``Gband = (A Phi^T)^{-1}``
    current across insert/evict with the exact splice + Woodbury window
    correction in ``core/gband_update.py`` — O(window) work plus two
    narrow banded solves per mutation instead of the O(n) RGF sweep.
    "full" recomputes the band with the RGF sweep every mutation (the
    pre-windowed behaviour; also the numerical escape hatch for extremely
    long mutation streams, where windowed roundoff accumulates).

    An explicit "windowed"/"full" wins; "auto" (the GPConfig default) and
    None defer to the process default (``set_gband`` / ``REPRO_GBAND``); a
    final "auto" means "windowed". ``fit()`` calls this once and bakes the
    result into the GP config, so jit caches key on the resolved mode.
    """
    g = gband if gband is not None else _gband
    if g not in GBAND_MODES:
        raise ValueError(
            f"unknown gband mode {g!r}; expected one of {GBAND_MODES}")
    if g == "auto":
        g = _gband
        if g not in GBAND_MODES:
            raise ValueError(
                f"unknown gband mode {g!r} (from {ENV_GBAND} or set_gband); "
                f"expected one of {GBAND_MODES}")
    if g == "auto":
        return "windowed"
    return g


def get_health() -> str:
    """Current process-wide serve-path health mode (may be "auto")."""
    return _health


def set_health(name: str) -> None:
    """Set the process-wide health mode ("auto" | "on" | "off")."""
    global _health
    if name not in HEALTH_MODES:
        raise ValueError(
            f"unknown health mode {name!r}; expected one of {HEALTH_MODES}")
    _health = name


@contextlib.contextmanager
def use_health(name: str):
    """Temporarily override the health mode (trace-time scope)."""
    prev = _health
    set_health(name)
    try:
        yield
    finally:
        set_health(prev)


def resolve_health(health: str | None = None) -> str:
    """Resolve the serve-path health mode to "on" | "off".

    "on" carries a ``HealthState`` on every fitted GP (solve verdicts, the
    Gband drift sentinel's accumulated truncation estimate) and lets the
    engines run the degradation ladder / quarantine path on bad verdicts.
    "off" drops the state entirely — the GP pytree has one fewer leaf and
    the serve path is bit-identical to the pre-health code.

    An explicit "on"/"off" wins; "auto" (the GPConfig default) and None
    defer to the process default (``set_health`` / ``REPRO_HEALTH``); a
    final "auto" means "on". ``fit()`` calls this once and bakes the result
    into the GP config, so jit caches key on the resolved mode.
    """
    h = health if health is not None else _health
    if h not in HEALTH_MODES:
        raise ValueError(
            f"unknown health mode {h!r}; expected one of {HEALTH_MODES}")
    if h == "auto":
        h = _health
        if h not in HEALTH_MODES:
            raise ValueError(
                f"unknown health mode {h!r} (from {ENV_HEALTH} or "
                f"set_health); expected one of {HEALTH_MODES}")
    if h == "auto":
        return "on"
    return h


def _core():
    # deferred: repro.core.banded lazily imports this module in its public
    # dispatchers, so neither side may import the other at module load
    from ..core import banded as bd

    return bd


# ---------------------------------------------------------------------------
# dispatched ops
# ---------------------------------------------------------------------------


def _flatten_batch(arrs, core_dims):
    """Broadcast leading batch dims and flatten them to one G axis.

    Every pallas kernel takes the flattened batch as its grid, so the whole
    stack is a single ``pallas_call`` (no trace-time unroll). Returns
    (batch, flats).
    """
    batch = jnp.broadcast_shapes(*[a.shape[:-d] for a, d in zip(arrs, core_dims)])
    flats = [
        jnp.broadcast_to(a, batch + a.shape[-d:]).reshape((-1,) + a.shape[-d:])
        for a, d in zip(arrs, core_dims)
    ]
    return batch, flats


@obs.scope("banded.matvec")
def banded_matvec(band, x, lo: int, hi: int, block: int = 512,
                  backend: str | None = None, n_active=None):
    """y = M x. band (..., n, lo+hi+1); x (..., n) or (..., n, k).

    ``n_active`` (traced, optional) marks capacity padding: the operands are
    canonicalized (identity-tail band, zero-tail x) so the result is exact on
    the active prefix and exactly zero on the tail.
    """
    bd = _core()
    n = band.shape[-2]
    mat_form = x.ndim >= 2 and x.shape[-2] == n and x.ndim == band.ndim
    if resolve_backend(backend, jnp.result_type(band, x)) == "jax":
        if n_active is not None:
            band = canonical_band(band, lo, hi, n_active)
            x = mask_rows(x, n_active, axis=-2 if mat_form else -1)
        return bd._matvec_scan(bd.Banded(band, lo, hi), x)
    xb = x if mat_form else x[..., None]
    batch, (bf, xf) = _flatten_batch((band, xb), (2, 2))
    out = banded_matvec_pallas(bf, xf, lo, hi, block=block,
                               interpret=interpret_kernels(), n_active=n_active)
    out = out.reshape(batch + out.shape[-2:])
    return out if mat_form else out[..., 0]


@obs.scope("banded.solve")
def banded_solve(band, rhs, lo: int, hi: int, pivot: bool = False,
                 backend: str | None = None, alg: str | None = None,
                 n_active=None):
    """Solve M x = rhs. band (..., n, w); rhs (..., n) or (..., n, k).

    On the pallas backend ``alg`` picks the kernel ("cr" block cyclic
    reduction when ``lo == hi`` — the default — vs "lu" row recurrence).
    On the jax backend "cr" means the log-depth ``cr_jax`` solve for an
    unpivoted ``lo == hi >= 1`` band of at least ``CR_MIN_BLOCK_ROWS``
    block rows; every other jax solve is the scan LU.
    ``pivot=True`` runs the pivoted block-CR kernel when the resolved
    algorithm is "cr"; otherwise it falls back to the jax gbsv-style scan
    (there is no pivoted LU kernel). With ``n_active`` the padded system is
    exactly ``blockdiag(M_active, I)`` with a zero RHS tail, so the solution
    is exact on the active prefix and zero on the tail.
    """
    bd = _core()
    n = band.shape[-2]
    vec_in = rhs.shape[-1] == n and rhs.ndim == band.ndim - 1
    be = resolve_backend(backend, jnp.result_type(band, rhs))
    use_cr = be == "pallas" and resolve_solve_alg(alg, lo, hi) == "cr"
    if be == "jax" or (pivot and not use_cr):
        if n_active is not None:
            band = canonical_band(band, lo, hi, n_active)
            rhs = mask_rows(rhs, n_active, axis=-1 if vec_in else -2)
        if (be == "jax" and not pivot and lo == hi >= 1
                and -(-n // lo) >= CR_MIN_BLOCK_ROWS
                and resolve_solve_alg(alg, lo, hi) == "cr"):
            obs.count("solve.cr")
            rb = rhs[..., None] if vec_in else rhs
            batch = jnp.broadcast_shapes(band.shape[:-2], rb.shape[:-2])
            x = block_cr_solve_jax(
                jnp.broadcast_to(band, batch + band.shape[-2:]),
                jnp.broadcast_to(rb, batch + rb.shape[-2:]), lo, pivot=False)
            return x[..., 0] if vec_in else x
        obs.count("solve.scan")
        return bd._solve_scan(bd.Banded(band, lo, hi), rhs, pivot=pivot)
    require_lowered("block_cr" if use_cr else "banded_lu")
    rb = rhs[..., None] if vec_in else rhs
    batch, (bf, rf) = _flatten_batch((band, rb), (2, 2))
    if use_cr:
        x = block_cr_solve_pallas(bf, rf, lo, pivot=pivot,
                                  interpret=interpret_kernels(), n_active=n_active)
    else:
        x = banded_solve_pallas(bf, rf, lo, hi, interpret=interpret_kernels(),
                                n_active=n_active)
    out = x.reshape(batch + x.shape[-2:])
    return out[..., 0] if vec_in else out


@obs.scope("banded.logdet")
def banded_logdet(band, lo: int, hi: int, pivot: bool = False,
                  backend: str | None = None, alg: str | None = None,
                  n_active=None):
    """log |det M|, batched over leading dims of band.

    Same algorithm selection as ``banded_solve``: block CR (with its exact
    Schur-telescoped log-determinant, pivoted or not) when the resolved alg
    is "cr"; the LU kernel otherwise, whose no-pivot elimination sends
    ``pivot=True`` callers to the pivoted jax scan. A canonical padding tail
    contributes exactly ``log|I| = 0``, so the capacity-wide reduction equals
    the active log-determinant.
    """
    bd = _core()
    be = resolve_backend(backend, band.dtype)
    use_cr = be == "pallas" and resolve_solve_alg(alg, lo, hi) == "cr"
    if be == "jax" or (pivot and not use_cr):
        band = canonical_band(band, lo, hi, n_active)
        return bd._logdet_scan(bd.Banded(band, lo, hi))
    require_lowered("block_cr" if use_cr else "banded_lu")
    batch, (bf,) = _flatten_batch((band,), (2,))
    if use_cr:
        ld = block_cr_logdet_pallas(bf, lo, pivot=pivot,
                                    interpret=interpret_kernels(),
                                    n_active=n_active)
    else:
        ld = banded_logdet_pallas(bf, lo, hi, interpret=interpret_kernels(),
                                  n_active=n_active)
    return ld.reshape(batch)


@obs.scope("banded.matmul")
def band_band_matmul(a_band, b_band, a_lo: int, a_hi: int, b_lo: int,
                     b_hi: int, block: int = 512, backend: str | None = None,
                     n_active=None):
    """C = A @ B in band form; returns band data (..., n, wa + wb - 1).

    Canonical padded operands multiply to ``blockdiag(C_active, I)``: the
    result's tail is again a canonical identity tail (at the wider band).
    """
    bd = _core()
    if resolve_backend(backend, jnp.result_type(a_band, b_band)) == "jax":
        a_band = canonical_band(a_band, a_lo, a_hi, n_active)
        b_band = canonical_band(b_band, b_lo, b_hi, n_active)
        return bd._band_band_matmul_scan(
            bd.Banded(a_band, a_lo, a_hi), bd.Banded(b_band, b_lo, b_hi)
        ).data
    batch, (af, bf) = _flatten_batch((a_band, b_band), (2, 2))
    out = band_matmul_pallas(af, bf, a_lo, a_hi, b_lo, b_hi, block=block,
                             interpret=interpret_kernels(), n_active=n_active)
    out = out.reshape(batch + out.shape[-2:])
    n = a_band.shape[-2]
    return out * bd._band_mask(n, a_lo + b_lo, a_hi + b_hi)


@obs.scope("kp.build")
def kp_gram(q: int, omega, xs, a_band, block: int = 512,
            backend: str | None = None):
    """Fused Phi = A K band assembly (Algorithm 2)."""
    if resolve_backend(backend, jnp.result_type(xs, a_band)) == "jax":
        from .ref import kp_gram_ref

        return kp_gram_ref(q, omega, xs, a_band)
    return kp_gram_pallas(q, omega, xs, a_band, block=block,
                          interpret=interpret_kernels())
