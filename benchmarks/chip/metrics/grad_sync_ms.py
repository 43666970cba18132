"""Device self time of the gradient reduction, ms per step: the ops under
``grad_sync``, every ``bucket_<k>`` of the MG-WFBP plan (pack, collective,
unpack, the parameter repack; ``core/comm.py``, ``core/bucketer.py``), the
mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.class_ms(ctx, "grad_sync")
