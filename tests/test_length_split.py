"""The length split: a batch's rows carved by token length across the declared
(rows, seq) grid (``bucketing.carve_by_length``) and served by
``tpu_inference`` as several steps, outputs back in the batch's row order."""

import asyncio
import json

import numpy as np
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Resource, ensure_plugins_loaded
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.tpu import bucketing
from arkflow_tpu.tpu.bucketing import BucketPolicy, carve_by_length

ensure_plugins_loaded()

#: bert-base's grid in ``bert_base.classify_backlog``
BBS = (8, 16, 32, 64, 128, 256, 512, 1024)
SBS = (32, 64, 128, 256, 512)


def cell_lengths(seed: int, n: int = 1024) -> np.ndarray:
    """A seeded draw of the cell's length distribution: log-normal, median
    48, sigma 1.0, clipped to [3, 510]."""
    rng = np.random.default_rng(seed)
    return np.clip(np.floor(48 * np.exp(rng.normal(0.0, 1.0, n))), 3, 510).astype(np.int64)


def slots(pieces) -> int:
    return sum(bb * sb for _, bb, sb in pieces)


def cost(pieces) -> int:
    """The carve's objective: slots dispatched plus a charge per step."""
    return slots(pieces) + bucketing.STEP_CHARGE_SLOTS * len(pieces)


def check_partition(pieces, lengths, bbs, sbs):
    """Every row in exactly one piece; every piece on the grid, wide enough
    for its longest row and padded the way the runner pads its row count."""
    policy = BucketPolicy(tuple(bbs), tuple(sbs))
    rows = np.concatenate([idx for idx, _, _ in pieces])
    assert sorted(rows.tolist()) == list(range(len(lengths)))
    for idx, bb, sb in pieces:
        assert bb in bbs and sb in sbs
        if len(pieces) == 1 and len(idx) > bb:
            # an unsplit batch over the top bucket: the runner chunks it
            assert bb == max(bbs)
            continue
        assert 0 < len(idx) <= bb
        assert bb == policy.batch_bucket(len(idx))
        assert sb == policy.seq_bucket(int(np.asarray(lengths)[idx].max()))


# -- the carve as a pure function ---------------------------------------------


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("n", [1, 7, 8, 100, 351, 1024, 1500])
def test_every_row_in_exactly_one_piece_on_the_grid(seed, n):
    lengths = cell_lengths(seed, n)
    check_partition(carve_by_length(lengths, BBS, SBS), lengths, BBS, SBS)


@pytest.mark.parametrize("bbs,sbs", [
    ((4, 8), (16, 32)), ((3, 7, 50), (10, 100)), ((1, 2, 4, 8), (8, 64)),
    ((256,), (32, 512)), ((8, 16, 32), (128,))])
def test_partition_on_odd_grids(bbs, sbs):
    rng = np.random.default_rng(len(bbs) * 131 + sbs[0])
    lengths = rng.integers(1, sbs[-1] + 1, size=211)
    check_partition(carve_by_length(lengths, bbs, sbs), lengths, bbs, sbs)


def test_no_rows_is_one_empty_piece():
    pieces = carve_by_length(np.zeros(0, np.int64), BBS, SBS)
    assert len(pieces) == 1 and len(pieces[0][0]) == 0


@pytest.mark.parametrize("n,lo,hi", [
    (1024, 33, 64), (351, 3, 32), (8, 300, 510), (5, 129, 256), (1500, 65, 128)])
def test_rows_sharing_a_seq_bucket_are_the_parents_one_step(n, lo, hi):
    """The parent pads the batch to (batch_bucket(n), seq_bucket(longest));
    the carve hands back exactly that, rows in their own order."""
    rng = np.random.default_rng(n)
    lengths = rng.integers(lo, hi + 1, size=n)
    policy = BucketPolicy(BBS, SBS)
    (idx, bb, sb), = carve_by_length(lengths, BBS, SBS)
    assert idx.tolist() == list(range(n))
    assert (bb, sb) == (policy.batch_bucket(n), policy.seq_bucket(int(lengths.max())))


def test_eight_mixed_rows_stay_one_step():
    """The per-step charge: a trickle split five ways would dispatch
    8 x (32 + 64 + 128 + 256 + 512) slots against 8 x 512 unsplit."""
    lengths = [20, 50, 100, 200, 400, 25, 60, 500]
    (idx, bb, sb), = carve_by_length(lengths, BBS, SBS)
    assert (len(idx), bb, sb) == (8, 8, 512)


@pytest.mark.parametrize("rows", [9, 16, 24])
def test_a_trickle_is_cut_only_where_a_step_pays_for_itself(rows):
    """One long row among short ones: cutting it out saves
    (bb x 512 - 8 x 512 - bb' x 32) slots, and is done only when that beats
    one more step's charge."""
    lengths = [500] + [10] * (rows - 1)
    pieces = carve_by_length(lengths, BBS, SBS)
    whole = BucketPolicy(BBS, SBS).batch_bucket(rows) * 512
    if len(pieces) > 1:
        assert slots(pieces) + bucketing.STEP_CHARGE_SLOTS * (len(pieces) - 1) < whole
    else:
        assert slots(pieces) == whole
    # rows 9..16 pad to 16 x 512 whole; 8 x 512 + 8 x 32 saves 3,840 slots
    assert len(pieces) == (2 if bucketing.STEP_CHARGE_SLOTS < 3840 else 1)


@pytest.mark.parametrize("seed", range(8))
def test_the_cells_reads_dispatch_under_a_third_of_the_slots(seed):
    lengths = cell_lengths(1000 + seed)
    pieces = carve_by_length(lengths, BBS, SBS)
    check_partition(pieces, lengths, BBS, SBS)
    assert slots(pieces) <= 0.32 * 1024 * 512
    assert 3 <= len(pieces) <= 16


def test_a_row_count_off_the_batch_grid_is_decomposed():
    """351 short rows behind a few long ones: several grid sizes at seq 32
    beat the one 512-row step their count pads to."""
    lengths = [400] * 8 + [20] * 351
    pieces = carve_by_length(lengths, BBS, SBS)
    check_partition(pieces, lengths, BBS, SBS)
    short = sorted(bb for _, bb, sb in pieces if sb == 32)
    assert (8, 512) in [(bb, sb) for _, bb, sb in pieces]
    # under the objective a 1,300-slot charge makes 128 + 256 (3 steps in
    # all) cheaper than 32 + 64 + 256 (4 steps); either beats 512
    assert short == [128, 256] and cost(pieces) < cost([(None, 8, 512), (None, 512, 32)])


@pytest.mark.parametrize("dp", [2, 4])
def test_a_dp_scaled_policy_gives_pieces_divisible_by_dp(dp):
    policy = BucketPolicy((8, 16, 32, 64, 128), SBS).dp_scaled(dp)
    lengths = cell_lengths(dp, 400)
    pieces = carve_by_length(lengths, policy.batch_buckets, policy.seq_buckets)
    check_partition(pieces, lengths, policy.batch_buckets, policy.seq_buckets)
    assert len(pieces) > 1
    assert all(bb % dp == 0 for _, bb, _ in pieces)


def test_a_whole_grid_compiled_changes_nothing():
    lengths = cell_lengths(5)
    grid = {(bb, sb) for bb in BBS for sb in SBS}
    free = carve_by_length(lengths, BBS, SBS)
    warm = carve_by_length(lengths, BBS, SBS, compiled=grid)
    assert [(i.tolist(), bb, sb) for i, bb, sb in free] == \
        [(i.tolist(), bb, sb) for i, bb, sb in warm]


def test_nothing_compiled_still_splits_a_read_worth_splitting():
    """The unsplit batch is the alternative (it compiles on first sight
    anyway): a read that dispatches a quarter of its slots is split."""
    lengths = cell_lengths(6)
    pieces = carve_by_length(lengths, BBS, SBS, compiled=set())
    assert len(pieces) > 1 and slots(pieces) <= 0.32 * 1024 * 512


def cold_programs(seed: int) -> frozenset:
    return frozenset((bb, sb) for _, bb, sb in carve_by_length(
        cell_lengths(seed), BBS, SBS, compiled=set()))


def test_cold_reads_of_one_traffic_name_the_same_few_programs():
    """A compile costs seconds, so a process that warmed nothing settles on
    a few large programs, and on the same ones whatever the read: a restart
    finds them in the compile cache."""
    sets = [cold_programs(7000 + seed) for seed in range(20)]
    assert all(len(s) <= 6 for s in sets)
    modal = max(set(sets), key=sets.count)
    assert sets.count(modal) >= 14
    assert modal == {(64, 512), (128, 256), (256, 128), (256, 64), (512, 32)}


@pytest.mark.parametrize("seed", range(5))
def test_later_reads_keep_to_the_programs_the_first_compiled(seed):
    """Once the first read's programs exist, reads of the same traffic are
    cut across them at the warm charge; another program is compiled only
    where it saves a cold step's charge in one batch, which is rare."""
    compiled = set(cold_programs(50 + seed))
    added = 0
    for k in range(12):
        lengths = cell_lengths(5000 + 100 * seed + k)
        pieces = carve_by_length(lengths, BBS, SBS, compiled=compiled)
        check_partition(pieces, lengths, BBS, SBS)
        assert slots(pieces) <= 0.32 * 1024 * 512
        new = {(bb, sb) for _, bb, sb in pieces} - compiled
        added += len(new)
        compiled |= new
    assert added <= 2


def test_a_cold_program_is_named_when_it_pays():
    """Only the top-left of the grid compiled; a batch of short rows with one
    long one gains far more than the threshold from a cold short step."""
    lengths = [500] * 8 + [10] * 1016
    compiled = {(1024, 512), (8, 512)}
    pieces = carve_by_length(lengths, BBS, SBS, compiled=compiled)
    assert (8, 8, 512) in [(len(i), bb, sb) for i, bb, sb in pieces]
    assert any((bb, sb) not in compiled for _, bb, sb in pieces)
    assert slots(pieces) < 0.1 * 1024 * 512


def test_the_tuners_waste_model_follows_the_split():
    """``tuner.predict_waste`` scores an unpacked grid by what the processor
    would dispatch: the carve's slots, not rows x the longest row's bucket."""
    from arkflow_tpu.tpu.tuner import ShapeConfig, SketchView, predict_waste

    lengths = cell_lengths(9, 2048)
    shape = ShapeConfig(BBS, SBS)
    waste, fill = predict_waste(SketchView(lengths, 0.0, len(lengths)), shape)
    cap = sum(slots(carve_by_length(lengths[i:i + 1024], BBS, SBS))
              for i in (0, 1024))
    assert fill == pytest.approx(lengths.sum() / cap)
    assert waste == pytest.approx(1 - fill) and waste < 0.5


# -- end to end through the processor, CPU-small BERT ---------------------------

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4, "ffn": 64,
             "max_positions": 64, "num_labels": 2}
GRID = {"batch_buckets": [8, 32, 128], "seq_buckets": [16, 64]}


def make_proc(**over):
    cfg = {"type": "tpu_inference", "model": "bert_classifier",
           "model_config": TINY_BERT, "max_seq": 64, "warmup": True,
           "outputs": ["label", "score"], **GRID, **over}
    return build_component("processor", cfg, Resource())


def texts(lengths) -> list[bytes]:
    """One text per token length (hash tokenizer: words + [CLS] + [SEP])."""
    return [" ".join(f"w{i}x{j}" for j in range(int(t) - 2)).encode()
            for i, t in enumerate(lengths)]


def mixed_batch(seed: int = 0, n: int = 120) -> tuple[MessageBatch, np.ndarray]:
    rng = np.random.default_rng(seed)
    lengths = np.where(rng.random(n) < 0.08, rng.integers(40, 64, n),
                       rng.integers(3, 16, n))
    lengths[0], lengths[-1] = 60, 5  # both classes, whatever the seed
    return MessageBatch.new_binary(texts(lengths)), lengths


def shapes(runner) -> dict[tuple, int]:
    return {dict(k)["input_ids"]: v for k, v in runner.dispatch_counts().items()}


def test_a_mixed_batch_is_served_split_and_row_for_row_as_unsplit():
    proc = make_proc()

    async def go():
        await proc.connect()  # warmup: true builds the whole grid
        runner = proc.runner
        grid = {(bb, sb) for bb in GRID["batch_buckets"] for sb in GRID["seq_buckets"]}
        assert runner.compiled_grid() == grid
        compiles = runner.m_compiles.value
        steps0 = (proc.m_steps.sum, proc.m_steps.count)
        batch, lengths = mixed_batch()
        (out,) = await proc.process(batch)
        # the same rows unsplit: the parent's one (128, 64) step
        ids, mask = proc.tokenizer.encode_batch(batch.to_binary("__value__"), 64)
        assert mask.sum(axis=1).tolist() == lengths.tolist()
        whole = runner.infer_sync({"input_ids": ids, "attention_mask": mask})
        return runner, compiles, steps0, out, whole

    runner, compiles, steps0, out, whole = asyncio.run(go())
    got_l = out.column("label").to_numpy()
    got_s = out.column("score").to_numpy()
    # near-tie rule: scores agree; labels agree wherever the score decides
    np.testing.assert_allclose(got_s, whole["score"], atol=1e-5)
    decided = np.abs(whole["score"] - 0.5) > 1e-4
    assert decided.sum() > 100
    assert (got_l == whole["label"])[decided].all()
    # row order is the read's
    assert out.to_binary("__value__") == mixed_batch()[0].to_binary("__value__")
    served = shapes(runner)
    served[(128, 64)] -= 1  # the unsplit comparison above
    served = {k: v for k, v in served.items() if v}
    assert len(served) >= 2 and (128, 64) not in served
    assert set(served) <= runner.compiled_grid()
    assert runner.m_compiles.value == compiles  # all warm: nothing compiled
    assert proc.m_steps.count - steps0[1] == 1
    assert proc.m_steps.sum - steps0[0] == sum(served.values())


@pytest.mark.parametrize("packing", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("form", [{"mesh": {"dp": 2}}, {"mesh": {"dp": 4}},
                                  {"device_pool": 2}],
                         ids=["dp2", "dp4", "pool2"])
def test_a_split_read_under_dp_or_a_pool_matches_one_device_row_for_row(form, packing):
    """The two forms that serve several chips: the read is carved on the
    FORM's grid (dp scales the batch buckets, a pool's member keeps them),
    its pieces run as several steps — each sharded over the mesh, or spread
    over the pool's members — and the rows come back in the read's order
    with the outputs one device gives."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    single = make_proc(packing=packing)
    served = make_proc(packing=packing, **form)
    batch, _ = mixed_batch(7)

    async def go():
        await single.connect()
        await served.connect()  # warm grid: the carve cuts at the warm charge
        (want,) = await single.process(batch)
        steps0 = (served.m_steps.sum, served.m_steps.count)
        shapes0 = shapes(served.runner) if "mesh" in form else {}
        (got,) = await served.process(batch)
        return want, got, steps0, shapes0

    want, got, steps0, shapes0 = asyncio.run(go())
    if not packing:  # the packed path carves row windows, not lengths
        assert served.m_steps.count - steps0[1] == 1
        assert served.m_steps.sum - steps0[0] >= 2  # really split
    if "mesh" in form:
        dp = form["mesh"]["dp"]
        ran = {k for k, v in shapes(served.runner).items() if v != shapes0.get(k, 0)}
        assert ran and all(rows % dp == 0 for rows, _ in ran)
    else:
        assert all(int(c.value) > 0 for c in served.runner.m_dispatch)
    assert got.to_binary("__value__") == batch.to_binary("__value__")
    want_s, got_s = want.column("score").to_numpy(), got.column("score").to_numpy()
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    decided = np.abs(want_s - 0.5) > 1e-4
    assert decided.sum() > 100
    assert (got.column("label").to_numpy() == want.column("label").to_numpy())[decided].all()


def test_padding_counters_read_the_gain():
    """``arkflow_tpu_tokens_total`` / ``_token_capacity_total`` are counted
    per dispatched step, so the padding share falls with the split."""
    proc = make_proc()
    batch, lengths = mixed_batch(3)

    async def go():
        await proc.connect()
        r = proc.runner
        t0, c0 = r.m_tokens.value, r.m_token_capacity.value
        await proc.process(batch)
        return r.m_tokens.value - t0, r.m_token_capacity.value - c0

    tokens, capacity = asyncio.run(go())
    assert tokens == int(lengths.sum())
    assert capacity < 0.5 * 128 * 64  # unsplit: one 128 x 64 step


def test_one_failing_piece_fails_the_batch_once():
    proc = make_proc()
    batch, _ = mixed_batch(1)

    async def go():
        await proc.connect()
        real = proc.runner.infer
        calls = []

        async def flaky(inputs):
            calls.append(inputs["input_ids"].shape)
            if inputs["input_ids"].shape[1] == 64:
                raise RuntimeError("step failed")
            return await real(inputs)

        proc.runner.infer = flaky
        with pytest.raises(RuntimeError, match="step failed"):
            await proc.process(batch)
        await asyncio.sleep(0.05)  # let the other pieces finish
        return calls

    calls = asyncio.run(go())
    assert len(calls) >= 2


def test_a_failing_piece_is_one_stream_error():
    """Through a stream: the batch is one failed batch (one error counted,
    its rows to the error output once), not one per piece."""
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.runtime import build_stream
    from tests.test_runtime import CollectOutput

    _, lengths = mixed_batch(2)
    cfg = StreamConfig.from_mapping({
        "input": {"type": "memory", "messages": [t.decode() for t in texts(lengths)]},
        "buffer": {"type": "memory", "capacity": 200, "timeout": "50ms"},
        "pipeline": {"thread_num": 1, "processors": [
            {"type": "tpu_inference", "model": "bert_classifier",
             "model_config": TINY_BERT, "max_seq": 64, **GRID}]},
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    sink, errors = CollectOutput(), CollectOutput()
    stream.output, stream.error_output = sink, errors
    proc = stream.pipeline.processors[0]
    real = proc.runner.infer

    async def flaky(inputs):
        if inputs["input_ids"].shape[1] == 64:
            raise RuntimeError("step failed")
        return await real(inputs)

    proc.runner.infer = flaky
    asyncio.run(stream.run(asyncio.Event()))
    assert sink.dropped_rows == 0
    assert errors.dropped_rows == len(lengths)
    assert proc.m_steps.count >= 1


@pytest.mark.parametrize("lo,hi,n", [(3, 16, 100), (20, 60, 30), (3, 10, 5)])
def test_rows_sharing_a_seq_bucket_dispatch_what_the_parent_dispatches(lo, hi, n):
    proc = make_proc(warmup=False)
    rng = np.random.default_rng(n)
    lengths = rng.integers(lo, hi + 1, n)
    lengths[0] = hi
    policy = BucketPolicy(tuple(GRID["batch_buckets"]), tuple(GRID["seq_buckets"]))

    async def go():
        steps = proc.m_steps.sum
        (out,) = await proc.process(MessageBatch.new_binary(texts(lengths)))
        return out, proc.m_steps.sum - steps

    out, steps = asyncio.run(go())
    assert out.num_rows == n and steps == 1
    assert shapes(proc.runner) == {
        (policy.batch_bucket(n), policy.seq_bucket(hi)): 1}


def test_tensor_rows_never_enter_the_carve():
    proc = build_component("processor", {
        "type": "tpu_inference", "model": "lstm_ae",
        "model_config": {"features": 2, "hidden": 8, "window": 8},
        "tensor_field": "window", "batch_buckets": [4, 8],
    }, Resource())
    rows = [json.dumps({"window": (np.ones(16) * 0.1 * i).tolist()}) for i in range(6)]
    batch = MessageBatch.from_pydict(
        {"window": [json.loads(r)["window"] for r in rows]})

    async def go():
        n0 = proc.m_steps.count
        (out,) = await proc.process(batch)
        return out, proc.m_steps.count - n0

    out, observed = asyncio.run(go())
    assert out.num_rows == 6 and observed == 0
    assert sum(proc.runner.dispatch_counts().values()) == 1


def test_packing_never_enters_the_carve():
    proc = make_proc(packing=True, warmup=False)
    batch, _ = mixed_batch(4)

    async def go():
        n0 = proc.m_steps.count
        (out,) = await proc.process(batch)
        return out, proc.m_steps.count - n0

    out, observed = asyncio.run(go())
    assert out.num_rows == batch.num_rows and observed == 0
    # the packed layout, not (rows, seq) pieces: every dispatch carries the
    # example-index arrays
    assert all("example_row" in dict(k) for k in proc.runner.dispatch_counts())


def test_without_warm_up_a_small_batch_is_not_worth_two_compiles():
    """Cold, the 120 mixed rows stay the one step they were (two programs
    to compile would cost more than the padding saves); once the grid is
    warm the same rows are split, and nothing compiles on the serving path."""
    proc = make_proc(warmup=False)
    batch, _ = mixed_batch(10)

    async def go():
        r = proc.runner
        assert r.compiled_grid() == set()
        await proc.process(batch)
        cold = set(r.compiled_grid())
        r.warm_shapes(r.buckets)  # off the serving path, as the tuner does
        compiles = r.m_compiles.value
        steps = proc.m_steps.sum
        (out,) = await proc.process(batch)
        return cold, proc.m_steps.sum - steps, compiles, r.m_compiles.value, out

    cold, steps, c0, c1, out = asyncio.run(go())
    assert cold == {(128, 64)}
    assert steps >= 2 and c1 == c0 and out.num_rows == 120


def test_a_device_pool_splits_over_what_every_member_compiled():
    proc = make_proc(device_pool=2)

    async def go():
        await proc.connect()
        batch, _ = mixed_batch(7)
        (out,) = await proc.process(batch)
        return out

    out = asyncio.run(go())
    assert out.num_rows == 120
    grid = {(bb, sb) for bb in GRID["batch_buckets"] for sb in GRID["seq_buckets"]}
    assert proc.runner.compiled_grid() == grid
    served = {dict(k)["input_ids"] for k in proc.runner.dispatch_counts()}
    assert len(served) >= 2 and served <= grid
