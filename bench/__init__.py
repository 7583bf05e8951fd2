"""Chip benchmark of the jax-nbtree served path (see bench/run.py)."""
