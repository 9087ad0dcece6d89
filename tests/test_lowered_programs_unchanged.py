"""What a new architecture adds to the shared model and engine code must not
reach the programs of the configurations that ran before it: the decode chunk
and the prefill chunk of the Llama, Mixtral and Keye layouts, lowered at test
sizes, are TEXTUALLY what they were at the commit before the hybrid model
(PR 34's tree, hashes taken there with this file's own function). A PR that
means to change one of these programs replaces its hash, and says so."""

import hashlib

import numpy as np
import pytest

from ray_tpu.serve.llm import LLMConfig, LLMServer

ENGINE = dict(paged=True, prefix_cache=True, max_batch_slots=4, page_size=8,
              max_seq_len=64, num_pages=40, decode_chunk=4)

# sha256 of `Lowered.as_text()` on PR 34's tree (commit 0bd2d65); Keye's
# prefill on PR 44's own tree (the child of d9cdbc9: no earlier commit
# produced that program), which meant to change it: its chunk writes the
# indexer's keys page by page, its radix select settles 3 bits a pass and
# ranks the tied only where a query has more of them than room, all on every
# backend; Keye's decode on PR 48's own tree, which meant to change it: the
# step scores the indexer's pool where it lies (the kernel
# `sparse_decode_scores`, interpreted off the TPU) and finds its k best by
# value (`top_k_places`) where it gathered the keys' pages, unpacked them and
# sorted the row; Keye's prefill and the four others are what they were
WAS = {
    ("tiny", "decode"): "6fa7d332a70e69c3e8fd168f46119afc148f3978f10561428672587614fd182c",
    ("tiny", "prefill"): "29c20d0a0f89cbbbf9acfc95013f9e783809ebdd16164101ab0c53c374e8be49",
    ("moe_tiny", "decode"): "becb1ff876b3b0522e250e5d0241215e9c2b9213e50e6e3521f028be2390d3c5",
    ("moe_tiny", "prefill"): "dcea1e4272b0700de5273db8c215f6016c54a4d1993498dc4e3da362e2c8ea8f",
    ("keye_tiny", "decode"): "595259172b0d00effd952e9b880a77f792c8926d6d23aab89929fa44a0cd243b",
    ("keye_tiny", "prefill"): "ef238414a9ec9571c8c91d6b49df1c4c843c1a3c5bec4ea3d3f3c75d3b992633",
}


def lowered_text(srv: LLMServer, program: str) -> str:
    if program == "decode":
        return srv.lower_decode_chunk().as_text()
    tokens = np.zeros((1, 16), np.int32)
    return srv._prefill.lower(srv.params, srv.cache, tokens, 0, np.int32(0),
                              np.int32(16), False).as_text()


@pytest.fixture(scope="module")
def servers():
    made = {}
    yield lambda preset: made.setdefault(
        preset, LLMServer(LLMConfig(preset=preset, **ENGINE)))
    for srv in made.values():
        srv.close()


@pytest.mark.parametrize("preset,program", sorted(WAS))
def test_program_is_textually_what_it_was(servers, preset, program):
    text = lowered_text(servers(preset), program)
    assert hashlib.sha256(text.encode()).hexdigest() == WAS[preset, program]


def test_the_hybrid_models_program_is_another(servers):
    """The check can tell: the new layout lowers to another decode program,
    and its cache carries what the others' does not."""
    srv = servers("solar_tiny")
    text = lowered_text(srv, "decode")
    assert hashlib.sha256(text.encode()).hexdigest() not in WAS.values()
    assert srv.cache.state is not None
    assert servers("moe_tiny").cache.state is None
