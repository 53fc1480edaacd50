"""The chip benchmark: one cell (configuration x traffic) per run.

See ``harness.py`` for how cells, configurations, traffic mixes and
metrics are found by name, and ``run.py`` for the command.
"""
