"""``fused_stencil_roofline`` in the mesh cells, where the kernel is
the shard-local one (strategy ``window``, the exchanged block as its
source): the reader beside this file, over all the chips."""
import os

from bench import harness

_reader = harness.load_module(
    "metrics", "fused_stencil_roofline",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

read = _reader.read
