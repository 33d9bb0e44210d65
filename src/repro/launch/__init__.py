"""repro.launch — mesh construction, train/serve entry points."""

from .mesh import make_test_mesh  # noqa: F401
