"""Operational tooling (``python -m tools.<name>``).

The scripts here are also directly runnable (``python tools/<name>.py``);
this package marker exists so daemon-style tools — the load generator,
notably — have a stable ``python -m tools.loadgen`` spelling for
supervisors and cron lines.
"""
