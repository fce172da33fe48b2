"""Launchers: make_production_mesh (mesh.py), the 512-device multi-pod
dry-run (dryrun.py — import sets XLA_FLAGS first), training and serving
CLIs (train.py / serve.py), the EXPERIMENTS.md table generator
(report.py), and the persistent compilation cache the entry points turn
on (cache.py)."""
