"""``ffat_sum_mesh4``: the ``ffat_sum`` graph with its window state
key-sharded over four chips.  Graph, stream and reference are
``ffat_sum``'s; the sizes and the mesh are in this configuration's
``.json``."""

from benchmark.harness import load_module

_base = load_module("configs", "ffat_sum")
make_ring = _base.make_ring
build_graph = _base.build_graph
expected = _base.expected
control = _base.control
compare = _base.compare
