# Pallas kernels for the TPU, one per module, each compared against its
# plain-JAX reference in ref.py. They lower through Mosaic by default;
# interpret=True runs them in the Pallas interpreter (CPU tests).
