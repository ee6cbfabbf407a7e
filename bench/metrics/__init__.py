"""One reader per metric: ``<name before the first dot>.py`` holds
``read(obs) -> float | None``, where ``obs`` is the window's
:class:`bench.generator.Observation`. A reader that finds nothing to read
returns None and the metric is left out of the run's line."""
