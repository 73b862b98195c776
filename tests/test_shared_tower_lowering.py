"""The Kimi and Phi towers lower to pinned programs: Phi's to the one it
lowered to before ``sambay``'s generic blocking was shared with
``afmoe.AfmoeLM``, Kimi's to the one it lowered to once
``hybridlm.RoutedLM`` was shared and ``moe.routed_experts`` moved a block
of consecutive tokens as one slice and a block's routing weights as one
slice of the sort: the StableHLO text of each loss and its gradients, at
the tests' small widths with the block sizes cut so that every blocked
path takes several blocks, hashed and pinned.  The text carries no source
locations, so a change that moves code without changing what it computes
keeps the pin; any change to what either tower computes breaks it, and is
then measured on the chip before it is re-pinned.

The pins hold for the JAX they were taken with: another JAX lowers other
text, so the test skips there until it is re-pinned (print the hashes
with ``lowered_hash``)."""

import hashlib

import jax
import pytest

import hybridlm_fixture
import sambay_fixture
from paddlebox_tpu.models import hybridlm, sambay
from paddlebox_tpu.parallel import moe

JAX_PINNED = "0.9.0"
CASES = {
    # Kimi's cell takes 4 sequences a step (two groups of KDA_SEQS)
    ("kimi", (24, 13, 1, 20)):
        "1cb8e218590e25b7e6033c6e3f77e4448c3fbb184bac523bbea2a488dee578e3",
    # Phi's takes 1; 3 take the blocked paths once more each
    ("phi", (24,)):
        "d60f49c1cdf0fa9b915353d5e47171ae3f8328f4ef519963eda339c28af5a249",
    ("phi", (24, 13, 1)):
        "96ce379a60edebe647e1bf4643e7e3243c8972e14010f4a5d5b44324617f1a10",
}


def lowered_hash(tower: str, lengths) -> str:
    fixture = hybridlm_fixture if tower == "kimi" else sambay_fixture
    model, params, _, _, args = fixture.seeded(fixture.config(), 0, lengths)
    f = jax.value_and_grad(lambda p, x: model.loss(p, x, *args[1:]),
                           argnums=(0, 1), has_aux=True)
    text = jax.jit(f).lower(params, args[0]).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("tower,lengths", list(CASES))
def test_the_shared_towers_lower_to_their_pinned_text(monkeypatch, tower,
                                                      lengths):
    if jax.__version__ != JAX_PINNED:
        pytest.skip(f"pinned under JAX {JAX_PINNED}, this is "
                    f"{jax.__version__}: re-pin")
    for name, value in (("KDA_CHUNK", 8), ("KDA_SUB", 4), ("KDA_BLOCK", 2),
                        ("MLA_QBLOCK", 4), ("HEAD_BLOCK", 8)):
        monkeypatch.setattr(hybridlm, name, value)
    monkeypatch.setattr(moe, "EXPERT_BLOCK", 16)
    for name, value in sambay_fixture.BLOCKS:
        monkeypatch.setattr(sambay, name, value)
    assert lowered_hash(tower, lengths) == CASES[(tower, lengths)]
