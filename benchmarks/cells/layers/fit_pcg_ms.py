"""fit_pcg_ms: device time of the fit's backfitting PCG (scope
``backfit.solve``, ``core/backfitting.solve_mhat``) per run of the fit
program (``_fit_impl``) that lies wholly inside the traced slice; the
variance's PCG, in ``posterior_var``, is left out (fit layer; device
trace)."""
import progtrace


def read(run):
    return progtrace.per_run_ms(run, "backfit.solve", program="_fit_impl")
