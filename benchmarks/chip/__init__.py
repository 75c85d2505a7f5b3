"""On-chip benchmark of the served path: harness, configurations, traffic
mixes, per-layer metric readers, the plain reference and the yardstick
(peaks, FLOP arithmetic, trace reduction). Entry point: ``run.py``."""
