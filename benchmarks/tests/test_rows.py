"""The chunked generator against the plain reference, row by row."""

import numpy as np
import pytest

from ddbench import rows, spec

ref = spec.load_module("reference", "rows")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
@pytest.mark.parametrize("seq,vocab", [(64, 512), (256, 32768), (33, 2)])
def test_token_shard_equals_reference(seed, seq, vocab):
    first, n = 5, 9
    tok, nxt = rows.token_shard(seed, first, n, seq, vocab)
    assert tok.dtype == nxt.dtype == np.int32
    assert tok.shape == nxt.shape == (n, seq)
    for i in range(n):
        want_tok, want_nxt = ref.token_row(seed, first + i, seq, vocab)
        assert np.array_equal(tok[i], want_tok), i
        assert np.array_equal(nxt[i], want_nxt), i
    assert np.array_equal(tok[:, 1:], nxt[:, :-1])
    assert 0 <= tok.min() and max(tok.max(), nxt.max()) < vocab


def test_rows_depend_on_seed_and_row_only():
    a, _ = rows.token_shard(3, 0, 8, 32, 512)
    b, _ = rows.token_shard(3, 4, 4, 32, 512)
    c, _ = rows.token_shard(4, 0, 8, 32, 512)
    assert np.array_equal(a[4:], b)
    assert not np.array_equal(a, c)


def test_token_ids_are_zipf_like():
    """An octave is as likely as any other, so low ids are far more common."""
    tok, _ = rows.token_shard(1, 0, 64, 1024, 32768)
    octave = np.floor(np.log2(tok.ravel() + 1)).astype(int)
    share = np.bincount(octave, minlength=15) / octave.size
    assert share.shape == (15,) and abs(share - 1 / 15).max() < 0.01


def test_vocab_out_of_range_is_refused():
    with pytest.raises(ValueError):
        rows.token_shard(0, 0, 1, 8, 1 << 17)
