"""Set-up: weights made a layer's leaves at a time, in the dtype that
the configuration states, so that it never holds more than the model
and one group; one program of the check for every seed; one untimed
group before the train window."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import serve, train, weights  # noqa: E402
from benchmark.harness import HERE, Harness  # noqa: E402

OTHER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "other_family")
REHEARSE = os.path.join(HERE, "testdata", "rehearse")


def _harness(folder):
    return Harness(folder, os.path.join(folder, "BENCHMARK.json"))


# --------------------------------------------------- weights, by groups
def _shapes(h, cell):
    cfg = h.cell(cell).config
    return h.family(cfg).param_shapes(cfg)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_leaves_made_in_groups_equal_leaves_made_at_once(
        harness, dtype, seed):
    import jax
    shapes = _shapes(harness, "tiny-lm.serve")
    in_groups = weights.make(shapes, seed, dtype)
    at_once = jax.jit(lambda key: weights.traced(shapes, key, dtype))(
        weights.fold(seed))
    assert sorted(in_groups) == sorted(shapes)
    for name, value in in_groups.items():
        assert value.dtype == dtype and value.shape == shapes[name][0]
        assert np.array_equal(
            np.asarray(value).view(np.uint8),
            np.asarray(at_once[name]).view(np.uint8)), name
    other_seed = weights.make(shapes, seed + 1, dtype)
    assert not np.array_equal(np.asarray(other_seed["dense0_weight"]),
                              np.asarray(in_groups["dense0_weight"]))


def test_groups_of_the_same_shapes_share_one_program(harness,
                                                      monkeypatch):
    """Or set-up pays a compilation a layer.  A group is a layer's
    leaves: the names that agree up to the end of their first
    number."""
    import jax
    shapes = _shapes(harness, "tiny-lm.train")
    groups = weights.groups(shapes)
    assert sorted(sum(groups, [])) == sorted(shapes)
    layers = [g for g in groups if g[0].startswith("transformerblock")]
    assert len(layers) == 2 and all(len(g) == 12 for g in layers)
    assert all(n.startswith("transformerblock1_") for n in layers[1])
    built, sound = [], jax.jit

    def counted(fn, **kwargs):
        built.append(fn)
        return sound(fn, **kwargs)

    monkeypatch.setattr(jax, "jit", counted)
    made = weights.make(shapes, 4)
    kinds = {tuple((tuple(shapes[n][0]), shapes[n][1]) for n in g)
             for g in groups}
    assert len(made) == len(shapes)
    assert len(built) == len(kinds) < len(groups)


@pytest.mark.parametrize("trace", [0, 1])
def test_one_untimed_group_goes_before_the_train_window(
        monkeypatch, measure, harness, trace):
    """The first burst of calls back to back after set-up is slower
    than every later one (PERF.md, PR 26): one group goes through the
    window's own call before ``setup_s`` is read, after the checked
    steps, and the window's count of steps leaves it out."""
    traffic = harness.cell("tiny-lm.train").traffic
    seen, sound = [], train.window

    def window(step, batches, seconds, fetch_every, **kwargs):
        out = sound(step, batches, seconds, fetch_every, **kwargs)
        seen.append((seconds, fetch_every, out[0], step._calls))
        return out

    monkeypatch.setattr(train, "window", window)
    result = measure("tiny-lm.train", seconds=0.2, trace=trace)
    assert result["correct"], result["compared"]
    checked, group = traffic["checked_steps"], traffic["fetch_every"]
    assert seen[0] == (0.0, group, group, checked + group)
    timed = seen[1:]
    assert len(timed) == 1 + trace
    assert result["attempted"] == timed[-1][2]
    assert timed[-1][3] == checked + group + sum(w[2] for w in timed)


def test_one_program_reads_the_change_for_every_seed(harness,
                                                      monkeypatch):
    """The seed's key is an argument of ``correct.change_norms``'s
    program, not a constant in it: with a constant the train cell
    compiled it anew (30 s on the chip) in every run whose seed the
    compile cache had not seen."""
    import jax
    from benchmark import correct
    shapes = _shapes(harness, "tiny-lm.train")
    texts, sound = [], jax.jit

    def lowering(fn, **kwargs):
        jitted = sound(fn, **kwargs)

        def call(*args):
            texts.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    seeds = (5, 2 ** 31 + 6)
    moved = [{n: v + 1 for n, v in weights.make(shapes, seed).items()}
             for seed in seeds]
    monkeypatch.setattr(jax, "jit", lowering)
    changes = [correct.change_norms(shapes, seed, now)
               for seed, now in zip(seeds, moved)]
    assert len(texts) == 2 and texts[0] == texts[1]
    for name, (shape, _) in shapes.items():     # every element moved by 1
        assert float(changes[0][name]) == pytest.approx(
            np.sqrt(np.prod(shape)), rel=1e-3), name


@pytest.mark.parametrize("folder,cell,dtype", [
    (REHEARSE, "tiny-lm.serve", "float32"),
    (OTHER, "tiny-other.serve", "bfloat16")],
    ids=["transformer_lm-float32", "other_lm-bfloat16"])
def test_set_up_never_holds_more_than_the_model_and_one_group(
        monkeypatch, folder, cell, dtype):
    """Through a stub of ``weights.in_groups``: the Parameters hold
    the stated dtype before any leaf is made, and no group is made
    while another waits to be written back."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel.functional import PureBlock
    h = _harness(folder)
    cfg = h.cell(cell).config
    fam = h.family(cfg)
    shapes = fam.param_shapes(cfg)
    assert cfg["serve"]["weights_dtype"] == dtype
    expected = weights.make(shapes, 6, dtype)
    waiting, most, seen = {}, [0], {}
    sound_groups, sound_write = weights.in_groups, PureBlock.write_back

    def watched(shapes, seed, dtype_asked):
        assert dtype_asked == dtype
        for group in sound_groups(shapes, seed, dtype_asked):
            assert not waiting, f"{list(group)} made while others wait"
            waiting.update({id(v): v.nbytes for v in group.values()})
            most[0] = max(most[0], sum(waiting.values()))
            yield group

    def write_back(self, params=None, states=None):
        if not seen:       # before the first leaf lands: what the
            #                block was initialized with
            seen.update({n: p.data()._data
                         for n, p in zip(self._names, self._objs)})
        for value in (params or {}).values():
            waiting.pop(id(value))
        return sound_write(self, params, states)

    monkeypatch.setattr(train.weights, "in_groups", watched)
    monkeypatch.setattr(PureBlock, "write_back", write_back)
    _, block, eng = serve.build(h, h.cell(cell), 6, mx)
    largest = max(sum(int(np.prod(shapes[n][0])) for n in g)
                  for g in weights.groups(shapes)) \
        * np.dtype(dtype).itemsize
    assert not waiting and 0 < most[0] <= largest
    assert len(seen) == len(shapes)
    assert all(v.dtype == dtype for v in seen.values())
    for name, p in block.collect_params().items():
        value = p.data()._data
        assert p.dtype == dtype and value.dtype == dtype
        assert np.array_equal(
            np.asarray(value).view(np.uint8),
            np.asarray(expected[name[len(block.prefix):]]).view(
                np.uint8)), name


@pytest.mark.parametrize("folder,cell,dtype", [
    (REHEARSE, "tiny-lm.serve", "float32"),
    (OTHER, "tiny-other.serve", "bfloat16")],
    ids=["transformer_lm-float32", "other_lm-bfloat16"])
def test_the_references_leaves_are_of_the_stated_dtype(
        monkeypatch, folder, cell, dtype):
    """A checkpoint published in bfloat16 holds bfloat16 numbers: the
    reference's weights are those numbers, widened where used."""
    h = _harness(folder)
    cfg = h.cell(cell).config
    fam = h.family(cfg)
    asked, sound = [], weights.make

    def make(shapes, seed, dtype_asked):
        asked.append(dtype_asked)
        made = sound(shapes, seed, dtype_asked)
        assert {v.dtype for v in made.values()} == {np.dtype(dtype)}
        return made

    monkeypatch.setattr(serve.weights, "make", make)
    rs = np.random.RandomState(2)
    sample = [(rs.randint(0, 256, 40).astype(np.int32),
               rs.randint(0, 256, 8).astype(np.int32))]
    numbers = serve.gaps(fam, cfg, 2, sample)
    assert asked == [dtype]
    assert numbers["far_gap_share"][0] > 1      # random tokens: far below
