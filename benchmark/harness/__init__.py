"""The benchmark's own code: everything that decides a number.

Nothing in this package names a cell, a model or a traffic mix. Those are
data files (``cells/``, ``configs/``, ``traffic/``, ``golden/``) and small
readers (``layer_metrics/``) that the harness finds by name.
"""
