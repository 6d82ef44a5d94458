"""A training job's rows are a pure function of the seed; every seed does
the same amount of work."""
import pytest

import bm_util  # noqa: F401
from benchmark import harness, traffic

JOB = harness.load_json(harness.HERE, "traffic", "train_1k.json")
BIG = 2**31 + 12345


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("seed", [1, BIG])
def test_training_rows_all_differ_and_follow_the_seed(seed, chips):
    job = dict(JOB, dataset_batches=2, batch_per_chip=2, seq_len=16)
    a = traffic.train_rows(job, seed, chips, 50257)
    assert a.shape == (4 * chips, 17) and a.min() >= 1 and a.max() < 50257
    assert len({r.tobytes() for r in a}) == 4 * chips
    assert (a == traffic.train_rows(job, seed, chips, 50257)).all()
    assert (a != traffic.train_rows(job, seed + 1, chips, 50257)).any()


def test_every_seed_gets_the_same_amount_of_work():
    a = traffic.train_rows(JOB, 1, 1, 50257)
    b = traffic.train_rows(JOB, BIG, 1, 50257)
    assert a.shape == b.shape == (32 * 16, 1025)
    assert a.dtype == b.dtype and (a != b).any()
