"""banded_solve_ms: mean device time of one call of the banded solve
(scope ``banded.solve``, ``kernels/ops.banded_solve``) lying wholly inside
the traced slice; a call is a maximal run of consecutive device operations
whose innermost scope is ``banded.solve`` (solve layer; device trace)."""
import progtrace


def read(run):
    return progtrace.mean_call_ms(run, "banded.solve")
