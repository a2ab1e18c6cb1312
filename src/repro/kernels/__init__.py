"""Pallas TPU kernels for model hot spots + jit'd dispatch wrappers.

Each kernel file pairs a ``pl.pallas_call`` + BlockSpec implementation with a
pure-jnp oracle in ``ref.py``; ``ops.py`` is the public API used by the model
zoo and switches between the XLA path (any backend, differentiable) and the
Pallas path (TPU target; validated on CPU with interpret=True).  Each
``pl.pallas_call`` is given ``name=``: it is the kernel's op name in a device
trace, so a refactor of the wrapper around it cannot rename the kernel.
"""
