"""Per-layer metric readers: ``<metric>.json`` names ``module:function``
here; a reader takes the run's context (``layers.Context``) and returns a
number, or None where it finds nothing to read."""
