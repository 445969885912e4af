"""The sparse-text cell's reading of
``fit.device_idle_pct``, where it moves ``fit_s.large``: the same reader."""
from benchlib.spec import reader_of

read = reader_of("fit.device_idle_pct")
