"""Pallas TPU kernels for the stack's compute hot-spots.

``onehot_scatter`` / ``rank_merge`` are the custom kernels (with
``ref.py`` pure-jnp references and ``ops.py`` dispatch wrappers);
``costmodel.py`` prices them for the autotuner.
"""
