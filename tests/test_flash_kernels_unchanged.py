"""The flash kernels the BERT cells compile are, as jaxprs, those of the
commit before grouped-query heads and the sliding window entered
``kernels/flash_attention.py`` (9c5a4c5, PR 34): the new forms are
specialisations chosen from the op's attributes, and a call without them
traces to the same program, kernel bodies, grids and index maps included.
(The lowered Mosaic payload is no yardstick: it carries source lines.)

The digests are of ``str(jaxpr)`` under jax 0.9.0 (``TAKEN_WITH``): another
jax may print the same program otherwise, and the failure then says so. That
they are the parent's is checked by running this file against the parent's
tree: ``git archive 9c5a4c5 | tar -x -C <dir>`` and ``PYTHONPATH=<dir> python
tests/test_flash_kernels_unchanged.py`` print these two lines.

A change that is MEANT to move these kernels regenerates the digests with
``python tests/test_flash_kernels_unchanged.py`` and says so in PERF.md:
``bert-large-s4096`` is the cell that then has to be measured."""
import hashlib

import jax
import jax.numpy as jnp
import pytest

TAKEN_WITH = "0.9.0"
GOLDEN = {
    (32, 16, 512, 64):
        "0aa2bd92407a5d1c2be3890f48155eb0b23c7c3e7a189bf129e2dd28d44a0079",
    (1, 16, 4096, 64):
        "85041c49426a5939ebb4ef62529fae3b37a5c1f280047c49653ed9f62a96d8f9",
}


def digest(shape) -> str:
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import _flash_blocks

    bq, bk = _flash_blocks(shape[2], shape[2])

    def loss(q, k, v):
        out = flash_attention(q, k, v, False, bq, bk, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("shape", sorted(GOLDEN), ids=["s4096-b1", "s512-b32"])
def test_bert_cells_flash_kernels_trace_as_before(shape):
    assert digest(shape) == GOLDEN[shape], (
        f"digests taken with jax {TAKEN_WITH}, this is jax {jax.__version__}"
        + ("" if jax.__version__ == TAKEN_WITH else
           ": the printer may have moved, not the kernels (regenerate on the "
           "parent's tree and compare)"))


if __name__ == "__main__":
    for shape in GOLDEN:
        print(shape, digest(shape))
