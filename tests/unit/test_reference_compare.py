"""``reference_compare.compare_leaves`` refuses what a model file's comparison
has to refuse: the shared loop cannot compare nothing."""
import numpy as np
import pytest

from . import reference_compare as compare


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"layers_0": {"q_proj_kernel": rng.normal(0, 1e-2, (8, 8)),
                         "norm": {"scale": rng.normal(0, 1.0, 8)}},
            "embed_tokens": rng.normal(0, 1e-3, (16, 8)),
            "gate": {"expert_bias": np.zeros(4)}}


FAULTS = {
    "one_leaf_off_by_1pc":
        lambda t: dict(t, embed_tokens=t["embed_tokens"] * 1.01),
    "without_a_leaf": lambda t: dict(t, layers_0={
        "q_proj_kernel": t["layers_0"]["q_proj_kernel"]}),
    "a_gradient_where_none_may_be":
        lambda t: dict(t, gate={"expert_bias": np.full(4, 1e-9)}),
    "one_leaf_reshaped": lambda t: dict(t, embed_tokens=t["embed_tokens"].T),
}
KW = dict(tol=2e-3, no_gradient=("['expert_bias']",))


@pytest.mark.parametrize("measure", ["max", "norm"])
def test_equal_trees_pass_and_every_leaf_but_the_bias_is_visited(measure):
    paths, small = compare.compare_leaves(_tree(), _tree(), measure=measure,
                                          **KW)
    assert [p[-1].key for p in paths] == ["embed_tokens", "scale",
                                          "q_proj_kernel"] and not small


@pytest.mark.parametrize("measure", ["max", "norm"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_on_either_side_is_refused(fault, measure):
    wrong = FAULTS[fault](_tree())
    for got, want in ((wrong, _tree()), (_tree(), wrong)):
        with pytest.raises(AssertionError):
            compare.compare_leaves(got, want, measure=measure, **KW)


@pytest.mark.parametrize("measure", ["max", "norm"])
def test_a_reference_leaf_of_zeros_and_an_empty_tree_are_refused(measure):
    dead = dict(_tree(), embed_tokens=np.zeros((16, 8)))
    with pytest.raises(AssertionError, match="embed_tokens"):
        compare.compare_leaves(dead, dead, measure=measure, **KW)
    with pytest.raises(AssertionError, match="no leaf"):
        compare.compare_leaves({}, {}, measure=measure, **KW)
    bias_alone = {"gate": {"expert_bias": np.zeros(4)}}
    with pytest.raises(AssertionError, match="nothing was compared"):
        compare.compare_leaves(bias_alone, bias_alone, measure=measure, **KW)


def test_a_vanishing_leaf_is_held_to_a_distance_and_named():
    """Xing's twelve: a reference norm under ``below`` is no denominator."""
    want = dict(_tree(), embed_tokens=np.full((16, 8), 1e-9))
    got = dict(want, embed_tokens=np.full((16, 8), 1.05e-9))    # 5% off
    with pytest.raises(AssertionError, match="embed_tokens"):
        compare.compare_leaves(got, want, measure="norm", **KW)
    paths, small = compare.compare_leaves(got, want, measure="norm",
                                          vanishing=(1e-6, 2e-8), **KW)
    assert list(small) == ["['embed_tokens']"] and len(paths) == 2
    assert small["['embed_tokens']"] == pytest.approx(5e-11 * 128 ** 0.5)
    with pytest.raises(AssertionError, match="embed_tokens"):
        compare.compare_leaves(got, want, measure="norm",
                               vanishing=(1e-6, 1e-10), **KW)


def test_rel_is_the_distance_over_the_references_norm():
    assert compare.rel([3.0, 4.0], [0.0, 4.0]) == pytest.approx(0.75)
