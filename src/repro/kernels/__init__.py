"""Pallas kernels for the framework's compute hot-spots, behind one
backend-dispatching layer (``dispatch.py`` + ``layout.py``).

kmeans_assign  — fused k-means assignment + statistics (paper's inner loop)
gmm_estep      — fused diagonal-GMM E-step + M-step sufficient statistics

Each package: kernel.py (pl.pallas_call + BlockSpec, restart-axis grid),
ops.py (public wrapper: per-backend padding + registry dispatch), ref.py
(pure-jnp oracle for tests).  Registered backends per op: ``tpu``
(Mosaic-compiled), ``gpu`` (Triton lowering, GPU tile policy),
``interpret`` (same kernel under the Pallas interpreter — the CPU CI
path), ``xla`` (reference contract).  ``dispatch.force_backend`` /
``register_backend`` let tests pin or extend any path.
"""
