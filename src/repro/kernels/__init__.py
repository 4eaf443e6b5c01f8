"""Pallas kernels for the contraction hot path (docs/ARCHITECTURE.md,
docs/MEGAKERNEL.md).

MXU-tiled GEMMs with fused operand transpose, N-step on-chip contraction
chains (``chain_n_pallas``), and the quantized (fp8/int8, scaled-epilogue)
variants — reached through :mod:`repro.core.plan_compiler`, never called
directly by model code.  Interpret mode runs every kernel on the CPU for
the tests; on a TPU they compile through Mosaic.
"""
