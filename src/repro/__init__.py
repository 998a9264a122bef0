"""Edge-MoE on TPU — production JAX framework.

The paper's five techniques as composable modules (``repro.core``), a
10-architecture model zoo (``repro.configs``/``repro.models``), Pallas TPU
kernels (``repro.kernels``), and the distributed substrate (data, optim,
checkpoint, train, serve, dist, launch, roofline).

Importing the package sets no platform: a process that forces host
devices (``--xla_force_host_platform_device_count``) sets
``JAX_PLATFORMS=cpu`` itself.
"""

__version__ = "1.0.0"
