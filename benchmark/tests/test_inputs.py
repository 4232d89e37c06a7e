"""The token generator: everything from the seed, labels the inputs
shifted by one, ranks that follow ``zipf_s``, documents closed by
``eos_id``; and what an example looks like for each kind of dataset."""

import numpy as np
import pytest

from benchmark import inputs

LM = dict(seq_len=32, vocab_size=1000, eos_id=7)
DATASET = {"kind": "tokens", **LM}
TRAFFIC = {"zipf_s": 1.0, "doc_len_median": 12, "doc_len_sigma": 0.8}


def _rows(seed, n=64, **change):
    kw = {**LM, **TRAFFIC, **change}
    return inputs.make_tokens(seed, n, **kw)


@pytest.mark.parametrize("seed", [0, 3, 2147483659])
def test_same_seed_same_rows_other_seed_other_rows(seed):
    a, b, c = _rows(seed), _rows(seed), _rows(seed + 1)
    assert a.dtype == np.int32 and a.shape == (64, 33)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < LM["vocab_size"]
    assert not np.array_equal(
        inputs.token_ids_by_rank(seed, 1000, 7),
        inputs.token_ids_by_rank(seed + 1, 1000, 7))


@pytest.mark.parametrize("global_batch, n", [(8, 3), (2, 5)])
def test_labels_are_the_inputs_shifted_by_one(global_batch, n):
    batches = list(inputs.host_batches(5, global_batch, n, DATASET, TRAFFIC))
    rows = _rows(5, n=n * global_batch).reshape(n, global_batch, 33)
    assert len(batches) == n
    for (x, y), r in zip(batches, rows):
        assert x.dtype == y.dtype == np.int32
        assert x.shape == (global_batch, 32)
        # token-major: a sequence's labels stay together
        assert y.shape == inputs.example_shapes(DATASET, global_batch)[1][0]
        assert y.shape == (global_batch * 32,)
        assert np.array_equal(x, r[:, :-1])
        y = y.reshape(global_batch, 32)
        assert np.array_equal(y, r[:, 1:])
        assert np.array_equal(x[:, 1:], y[:, :-1])


@pytest.mark.parametrize("zipf_s", [0.0, 0.7, 1.0, 1.3])
def test_rank_frequencies_follow_zipf_s(zipf_s):
    rows = _rows(11, n=8192, zipf_s=zipf_s, doc_len_median=None,
                 doc_len_sigma=None)
    content = rows[:, :-1].ravel()             # the last place is the EOS
    assert not np.any(content == LM["eos_id"])
    by_rank = inputs.token_ids_by_rank(11, LM["vocab_size"], LM["eos_id"])
    assert sorted(by_rank) == [i for i in range(1000) if i != 7]
    counts = np.bincount(content, minlength=1000)[by_rank]
    want = np.arange(1, 1000, dtype=np.float64) ** -zipf_s
    want *= content.size / want.sum()
    # the 50 most frequent ranks, each within five standard errors
    top = slice(0, 50)
    assert np.all(np.abs(counts[top] - want[top])
                  <= 5 * np.sqrt(want[top]) + 1)
    # and the slope of log frequency against log rank over them
    slope = np.polyfit(np.log(np.arange(1, 51)),
                       np.log(counts[top] + 0.5), 1)[0]
    assert slope == pytest.approx(-zipf_s, abs=0.05)


def test_eos_separates_documents_of_the_stated_lengths():
    rows = _rows(2, n=4096, seq_len=32, doc_len_median=6, doc_len_sigma=0.5)
    stream = rows.ravel()
    eos = np.flatnonzero(stream == LM["eos_id"])
    lengths = np.diff(np.concatenate([[-1], eos])) - 1
    assert lengths.min() >= 1 and lengths.max() <= 32
    assert np.median(lengths) == pytest.approx(6, abs=1)
    # log-normal: the log of the lengths spreads by sigma (rounding and
    # the clip at 1 take a little off)
    assert np.std(np.log(lengths)) == pytest.approx(0.5, abs=0.1)
    # a null median: one document a row, its EOS in the row's last place
    whole = _rows(2, n=16, doc_len_median=None, doc_len_sigma=None)
    assert np.all(whole[:, -1] == LM["eos_id"])
    assert not np.any(whole[:, :-1] == LM["eos_id"])


def test_documents_longer_than_a_row_are_clipped_to_it():
    rows = _rows(4, n=256, seq_len=8, doc_len_median=100, doc_len_sigma=0.1)
    assert np.all(rows[:, -1] == LM["eos_id"])      # every length is 8


@pytest.mark.parametrize("dataset, inputs_want, labels_want", [
    ({"kind": "images", "image_size": 32, "num_classes": 10},
     ((6, 32, 32, 3), np.float32), ((6,), np.int32)),
    (DATASET, ((6, 32), np.int32), ((192,), np.int32)),
])
def test_example_shapes_by_kind(dataset, inputs_want, labels_want):
    assert inputs.example_shapes(dataset, 6) == (inputs_want, labels_want)
    sample = inputs.sample_input(dataset)
    assert sample.shape == (1,) + inputs_want[0][1:]
    assert sample.dtype == inputs_want[1]


def test_image_batches_are_what_the_program_normalises():
    dataset = {"kind": "images", "image_size": 8, "num_classes": 5}
    batches = list(inputs.host_batches(9, 4, 2, dataset, {}))
    again = list(inputs.host_batches(9, 4, 2, dataset, {}))
    assert len(batches) == 2
    for (x, y), (x2, y2) in zip(batches, again):
        assert x.shape == (4, 8, 8, 3) and x.dtype == np.float32
        assert y.shape == (4,) and y.max() < 5
        assert np.array_equal(x, x2) and np.array_equal(y, y2)


@pytest.mark.parametrize("dataset, traffic, want", [
    ({"kind": "images", "image_size": 8, "num_classes": 5}, {},
     {"input.batch": 3}),
    (DATASET, TRAFFIC, {"input.pool": 1, "input.batch": 3}),
], ids=["images", "tokens"])
def test_the_making_of_each_batch_is_a_span_of_the_harness(
        dataset, traffic, want):
    """Under the arm ``setup``: round ``get_batch`` for images; for tokens
    round the pool's one draw and round each batch's cut. The batches are
    what they are without a recorder."""
    from benchmark.spans import Spans
    spans = Spans()
    timed = list(inputs.host_batches(5, 4, 3, dataset, traffic, spans))
    plain = list(inputs.host_batches(5, 4, 3, dataset, traffic))
    assert all(np.array_equal(a, b) for pair, again in zip(timed, plain)
               for a, b in zip(pair, again))
    got = spans.seconds()
    assert set(got) == {"setup"}
    assert {name: len(secs) for name, secs in got["setup"].items()} == want
    assert all(s > 0 for secs in got["setup"].values() for s in secs)
