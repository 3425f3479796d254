"""The warm cell's files and readers: the mistral-large configuration is
the program's own model cut in depth alone, the steady mix draws the
lengths it states, the program's spans of a warm window are read from the
harness's own mark, and the scheduler's queue wait is read from the
request rows."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

from bench import harness, traffic  # noqa: E402
from bench.arch import arch_of, load_config, program_config  # noqa: E402
from repro.configs.mistral_large_123b import CONFIG  # noqa: E402
from repro.utils import spans  # noqa: E402
from test_bench_program_spans import SPANS, T0, _record, _recorder  # noqa: E402

WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "vocab_size",
          "rope_theta", "sliding_window", "family")


def test_config_is_the_program_model_cut_in_depth_alone():
    conf = load_config("mistral-large-123b-3L")
    ours = program_config(arch_of(conf))
    for key in WIDTHS:
        assert getattr(ours, key) == getattr(CONFIG, key), key
    assert ours.norm_eps == 1e-5  # published; the program's preset leaves its default
    assert ours.dtype == ours.param_dtype == "bfloat16"
    assert ours.moe is None and not conf["tie_word_embeddings"]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["published"] == {"num_hidden_layers": CONFIG.num_layers} and ours.num_layers == 3
    assert conf["source"].endswith(CONFIG.source.removeprefix("hf:"))


def test_config_bytes_as_reckoned():
    """3 layers of 2,768,289,792 B, 1,610,612,736 B of embedding and head
    and the final norm; the cache of 32 slots over the mix's longest
    request."""
    a = arch_of(load_config("mistral-large-123b-3L"))
    layer = 2 * (a.d_model * (a.heads + 2 * a.kv_heads) * a.head_dim
                 + a.heads * a.head_dim * a.d_model + 3 * a.d_model * a.d_ff + 2 * a.d_model)
    assert layer == 2_768_289_792
    assert 3 * layer + 2 * 2 * a.vocab * a.d_model + 2 * a.d_model == 9_915_506_688
    mix = traffic.load_mix("steady_chat")
    cache = mix["slots"] * traffic.max_seq(mix) * a.layers * 2 * 2 * a.kv_heads * a.head_dim
    assert cache == 1_006_632_960


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**40 + 9])
def test_steady_chat_draws_the_stated_lengths(seed):
    mix = traffic.load_mix("steady_chat")
    assert mix["start"] == "warm" and mix["slots"] == 32
    reqs = traffic.schedule(mix, seed, 51, 32768)
    assert len(reqs) == round(mix["rate_rps"] * 51) and reqs[0].due == 0.0
    lens = Counter(len(r.prompt) for r in reqs)
    assert set(lens) == {128, 512, 1024, 2048}
    # each block of 16 holds the largest-remainder split of the weights
    block = Counter(len(r.prompt) for r in reqs[:16])
    assert block == Counter({128: 1, 512: 5, 1024: 5, 2048: 5})
    outs = np.array([r.out_len for r in reqs])
    assert outs.min() >= 16 and outs.max() <= 512
    assert 110 <= np.median(outs) <= 150  # lognormal of median 129
    gaps = np.diff([r.due for r in reqs])
    assert abs(1 / gaps.mean() - mix["rate_rps"]) < 0.1 * mix["rate_rps"]


def test_a_warm_mix_warms_its_groups_alone():
    """A warm window opens on an empty queue: set-up warms each length at
    up to ``warm_group`` requests an admission, whatever the first
    requests hold; a cold mix also covers its first round."""
    mix = traffic.load_mix("steady_chat")
    reqs = traffic.schedule(mix, 3, 51, 32768)
    assert set(harness.warm_groups(mix, reqs)) == {
        (g, S) for S in mix["prompt_lens"] for g in range(1, mix["warm_group"] + 1)}
    cold = {**mix, "start": "cold"}
    first = Counter(len(r.prompt) for r in reqs[: mix["slots"]])
    assert max(g for g, _ in harness.warm_groups(cold, reqs)) == \
        max(mix["warm_group"], *first.values())


# -- the program's spans of a warm window ----------------------------------------

@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans, "RECORDER", _recorder(sorted(SPANS, key=lambda r: r.end)))


NAMES = ("tier1_read_s", "tier1_decode_s", "tier1_install_s", "tier1_wait_s",
         "decode_wait_ms", "decode_host_ms")


def _read(name, rec):
    return harness.load_reader(name)(rec)


def test_warm_window_reads_from_the_harness_mark(recorded):
    """With no cold start the window opens at ``Record.t_open``: the same
    spans read the same numbers as from a cold start at that time, and a
    mark later by 15 s leaves the first step out."""
    cold = _record()
    warm = _record(cold=None)
    warm.t_open = T0
    for name in NAMES:
        assert _read(name, warm) == _read(name, cold), name
    late = _record(cold=None, seconds=40.0)  # [15, 55): no fault is in it
    late.t_open = T0 + 15.0
    assert _read("tier1_read_s", late) == 0.0
    # decode steps (wall, wait): (0.1, 0.06), (0.2, 0.1), (0.09, 0.04)
    assert _read("decode_wait_ms", late) == pytest.approx(60.0)


def test_a_cold_window_ignores_the_harness_mark(recorded):
    """A cold cell reads from its cold start's ``t_start`` exactly as
    before, wherever the harness's mark lies; a cold report without the
    span clock still reads nothing."""
    for name in NAMES:
        plain = _read(name, _record())
        marked = _record()
        marked.t_open = T0 + 15.0
        assert _read(name, marked) == plain, name
        nomark = _record(cold={"upload_s": 9.0})
        nomark.t_open = T0
        assert _read(name, nomark) is None


# -- the scheduler's queue wait -----------------------------------------------

def _row(due, admitted):
    return {"due": due, "admitted": admitted}


def test_queue_wait_p95_reads_the_rows():
    rec = _record(cold=None, seconds=10.0)
    rec.requests = [_row(d, d + w) for d, w in zip(range(10), np.arange(10) / 100.0)]
    # waits 0, 10, ..., 90 ms; numpy's linear percentile: 85.5 ms
    assert harness.load_reader("queue_wait_p95_ms")(rec) == pytest.approx(85.5)
    # a request still queued at the close counts with its wait so far
    rec.requests.append(_row(9.5, None))
    waits = np.append(np.arange(10) / 100.0, 0.5)
    assert harness.load_reader("queue_wait_p95_ms")(rec) == \
        pytest.approx(1e3 * np.percentile(waits, 95))


def test_queue_wait_reads_nothing_where_nothing_was_admitted():
    rec = _record(cold=None)
    assert harness.load_reader("queue_wait_p95_ms")(rec) is None
    rec.requests = [_row(0.0, None), _row(1.0, None)]
    assert harness.load_reader("queue_wait_p95_ms")(rec) is None
