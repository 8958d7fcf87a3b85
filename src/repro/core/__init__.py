"""PQS core: prune, quantize, and sort for low-bitwidth accumulation.

Import the submodules directly (``repro.core.dispatch.pqs_dot``, ...):
``core.dispatch`` imports the kernel package, whose modules import
``core`` submodules, so re-exporting it here would make
``import repro.kernels.ops`` on its own circular.
"""
