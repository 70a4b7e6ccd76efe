"""``glue_busy_share`` in the mesh cells, where the glue holds the
shard-local padded-window copies (the exchanged faces' concatenates,
the unsharded axis's boundary pad) besides the scan's copies: the
reader beside this file, over all the chips."""
import os

from bench import harness

_reader = harness.load_module(
    "metrics", "glue_busy_share",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

read = _reader.read
