from .mesh import make_host_mesh
