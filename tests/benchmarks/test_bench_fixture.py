"""The seeded CIFAR-10-format fixture, read back through the program's
own loader."""

import numpy as np
import pytest

from benchmarks.harness import fixture
from benchmarks.harness.spec import BENCH_DIR, load_json
from fast_autoaugment_tpu.data.datasets import load_dataset

SPEC = load_json(f"{BENCH_DIR}/fixtures/cifar10_templates.json")


@pytest.fixture(scope="module")
def full(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("fixture"))
    fixture.write_fixture(root, SPEC, seed=11)
    return root


def test_cifar10_reads_it_at_cifar10s_size(full):
    train, test = load_dataset("cifar10", full)
    assert train.images.shape == (50000, 32, 32, 3) and train.images.dtype == np.uint8
    assert test.images.shape == (10000, 32, 32, 3)
    assert train.labels.dtype == np.int32 and set(train.labels) == set(range(10))
    assert not train.lazy  # eager: the trainer's device cache takes it


def test_reduced_cifar10_reads_the_papers_4000(full):
    train, test = load_dataset("reduced_cifar10", full)
    assert len(train) == 4000 and len(test) == 10000
    assert np.bincount(train.labels).min() >= 380  # stratified


def test_it_is_learnable_and_the_same_data_set_under_every_seed(full, tmp_path):
    """A class is its template whatever the seed (so a fold checkpoint of
    one run serves another's images), and the template is recoverable:
    the mean image of a class correlates with its own template alone."""
    train, _ = load_dataset("cifar10", full)
    templates = np.repeat(np.repeat(fixture.class_templates(SPEC), 8, 1), 8, 2)
    means = np.stack([train.images[train.labels == c][:500].mean(axis=0)
                      for c in range(10)])
    means -= means.mean(axis=(1, 2, 3), keepdims=True)
    score = np.einsum("chwk,dhwk->cd", means, templates)
    assert (score.argmax(axis=1) == np.arange(10)).all()
    small = dict(SPEC, train=500, test=100)
    _, labels_a = fixture.make_split(small, 500, np.random.default_rng(1))
    images_b, labels_b = fixture.make_split(small, 500, np.random.default_rng(2))
    assert (labels_a != labels_b).any()
    means_b = np.stack([images_b[labels_b == c].mean(axis=0) for c in range(10)])
    means_b -= means_b.mean(axis=(1, 2, 3), keepdims=True)
    assert (np.einsum("chwk,dhwk->cd", means_b, templates).argmax(axis=1)
            == np.arange(10)).all()


def test_two_seeds_differ_and_one_seed_repeats(tmp_path):
    small = dict(SPEC, train=500, test=100)
    roots = [str(tmp_path / name) for name in ("a", "b", "c")]
    for root, seed in zip(roots, (5, 5, 6)):
        fixture.write_fixture(root, small, seed)
    a, b, c = (load_dataset("cifar10", r) for r in roots)
    assert (a[0].images == b[0].images).all() and (a[1].labels == b[1].labels).all()
    assert (a[0].images != c[0].images).any() and (a[0].labels != c[0].labels).any()
    # another seed into the same place replaces the data; the same seed does not
    fixture.write_fixture(roots[0], small, 6)
    assert (load_dataset("cifar10", roots[0])[0].images == c[0].images).all()


def test_a_bad_spec_is_refused():
    with pytest.raises(ValueError, match="noise_levels"):
        fixture.make_split(dict(SPEC, noise_levels=100), 10,
                           np.random.default_rng(0))
    with pytest.raises(ValueError, match="batch files"):
        fixture.write_fixture("/nonexistent-never-written", dict(SPEC, train=501), 0)
