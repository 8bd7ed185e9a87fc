"""The chip benchmark of the stencil system: ``python3 perfbench/run.py``."""
