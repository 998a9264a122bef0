"""Distribution layer: meshes, sharding rules, compressed collectives.

``dist.sharding`` holds the mesh constructor (``make_mesh``, Auto axes)
and the one layout table every (arch × mesh) cell shares — logical
activation constraints (``constrain``/``use_rules``), regex parameter
patterns (``param_sharding_rules``), and the derived batch/optimizer-state
tables.  ``dist.compress`` holds the int8 error-feedback gradient
collectives used for the cross-pod all-reduce.
"""

from repro.dist.sharding import make_mesh

__all__ = ["make_mesh"]
