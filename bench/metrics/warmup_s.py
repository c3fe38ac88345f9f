"""Host seconds of the flow warm-up and C_D0 calibration in the run's
set-up: the ``repro/setup.warmup`` spans of the program's last ``train()``
(one per geometry), summed from the record ``repro.core.spans`` keeps of
them.  A program without that record reads nothing."""
SPAN = "repro/setup.warmup"


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:
        return None
    seconds = getattr(spans, "setup_seconds", {}).get(SPAN)
    return float(sum(seconds)) if seconds else None
