"""Cell benchmark of the verdict sidecar: one configuration under one
traffic mix per cell, driven through ``SidecarClient`` ->
``VerdictService`` and checked against a plain reference.  See
``run.py``."""
