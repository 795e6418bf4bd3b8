"""The ``dense_gqa`` layout lays the same weights into the program's tree
as the harness did before layouts existed: the tiny tree at seed 5, leaf
by leaf, against SHA-256 digests of its bytes recorded from that earlier
code (``program.dense_params``, with ``weights.dense_layer``)."""
import hashlib

import jax
import numpy as np

import common
import program
import tiny

SEED5 = {
    "embed/w": "3a60d65f7026e2bf4deb10cdac7b30eb0811f274bb8b56b2c04c70bcfb02ef62",
    "final_norm/scale": "2a784dd95db5041ee0a815bb16b74bd8b9a4d37510f2f4739eab2ff699300ab1",
    "head/w": "264cc40db48c87e87b21742833b89f79ad0fe3da15958e07fe24a1979cf1e640",
    "layers/sub_0/ffn/down/w": "d7af4c52df01b16c5dde4aaeb3178eb86c40bcccfb7814f1164ad792372f90d8",
    "layers/sub_0/ffn/gate/w": "98cfe11a80ef8df4c5433d241f57093dbdb5603a538679e2368fbb1f8447a944",
    "layers/sub_0/ffn/up/w": "2af023341b70eea4fc8c367de246527e3f26b5a6e286412b5528aec2ed4436ab",
    "layers/sub_0/mixer/kv/w": "0894e2787132fba92ec1867de4263658bc6f89fc05066db022c8c836f2b28d6d",
    "layers/sub_0/mixer/o/w": "54d00c37f3deaca9ebee74c51289be626e690fd443f46d2ab9ff45f3dfd49e4a",
    "layers/sub_0/mixer/q/w": "e6809c016e623f5c18e3f1bfaf60462d3dc91a546d4f9e860f3d7cdef4feafa7",
    "layers/sub_0/norm1/scale": "e5b12c08ce50e79ce77f975efb6295176c21740ed2d1954fe873d32da460dfdc",
    "layers/sub_0/norm2/scale": "041ba559eceaf6a111ce09180a3772037b8d1fe5b015b833868bfddd5f227e74",
}


def test_tiny_dense_tree_bytes_unchanged():
    c = tiny.serve_config()
    assert c["program"]["layout"] == "dense_gqa"
    params = program.params(c, program.build(c), 5)
    got = {common.leaf_name(path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == SEED5
