# Pallas TPU kernels for the framework's compute hot-spots + the paper's
# data-path hot-spot (flit packing).  Each subpackage: kernel.py
# (pl.pallas_call + BlockSpec VMEM tiling), ops.py (jit'd wrapper with a
# backend switch), ref.py (pure-jnp oracle).  Kernels are validated on CPU
# with interpret=True; the XLA path (ref) is used when lowering for
# non-TPU backends (e.g. the CPU dry-run).  flit_sim holds only ref.py:
# the period-exact probe bodies the adaptive flit simulators compile
# through XLA.
