"""The sparse-text cell's reading of ``build.device_ms_per_fit``,
where it moves ``fit_s.large``: the same reader."""
from benchlib.spec import reader_of

read = reader_of("build.device_ms_per_fit")
