"""The plain reference's padding to fixed shapes changes none of the
logits of the real requests."""
import json
import os

import numpy as np

from chipbench import corpus as corpus_lib
from chipbench.configs import dense_ref
from chipbench.tests import helpers


def test_padding_rows_and_positions_changes_nothing():
    with open(os.path.join(helpers.DATA, "tiny.json")) as f:
        conf = json.load(f)
    conf["torch_dtype"] = "float32"
    rng = np.random.default_rng(5)
    corpus = corpus_lib.corpus_tokens(2 * conf["moska"]["chunk_size"],
                                      conf["vocab_size"], 11)
    seqs = [(rng.integers(0, conf["vocab_size"], n).astype(np.int32),
             rng.integers(0, conf["vocab_size"], g).astype(np.int32))
            for n, g in [(40, 9), (150, 5), (7, 12)]]
    plain = np.asarray(dense_ref.logits(conf, 11, corpus, seqs))
    padded = np.asarray(dense_ref.logits(conf, 11, corpus, seqs, rows=5,
                                         seq_len=384))
    assert plain.shape == padded.shape == (26, conf["vocab_size"])
    np.testing.assert_allclose(padded, plain, rtol=0, atol=1e-4)
