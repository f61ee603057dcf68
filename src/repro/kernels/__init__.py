"""Pallas TPU kernels for the framework's compute hot-spots.

Layout per kernel: <name>.py holds the pl.pallas_call + BlockSpec tiling,
ref.py the pure-jnp oracle, ops.py the jit'd dispatch wrapper. Dispatch is
one shared policy (``ops.resolve_impl``): ``REPRO_PALLAS_INTERPRET=1`` wins
everywhere (interpret mode, bit-faithful to the kernel body, TPU included),
else TPU runs the compiled kernel, else the oracle. Validated by shape/dtype
sweeps in tests/test_kernels.py.

``beam_step.py`` is the fused graph-walk hop (neighbor gather + ADC/exact
distances + beam top-k merge + visited update in one launch per 8 query
lanes); the step-kernel layer in :mod:`repro.core.search` plugs it into
fixed-beam, probe, and continue via ``ops.beam_walk``, which never falls
back to the oracle — off-TPU it runs interpret-mode so the fused arithmetic
is always what executes.  Every kernel here compiles for a v5e at the
serving widths (``tests/test_tpu_compile.py``).
"""
from repro.kernels import ops  # noqa: F401
