"""rgf_sweep_ms: mean device time of one full RGF sweep of the variance
band (scope ``band_inverse.rgf``, ``core/band_inverse.inverse_band``), as
each evict and insert runs it once under ``gband="full"``: the scope's
device time per mutation program run wholly inside the traced slice
(mutation layer; device trace)."""
import progtrace


def read(run):
    return progtrace.per_run_ms(run, "band_inverse.rgf")
