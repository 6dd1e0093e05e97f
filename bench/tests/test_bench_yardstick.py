"""The benchmark's own arithmetic on the CPU: FLOP counts, peaks, traffic,
configuration files and the BENCHMARK.json contract."""
import json
import re
import statistics

import jax
import pytest

from bench import flops, harness, model, peaks, traffic
from bench.tests import cells

BENCH = harness.benchmark()
CONFIGS = sorted(p.stem for p in (harness.BENCH / "configs").glob("*.json"))


def cfg_named(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


# -- FLOP count -----------------------------------------------------------

def test_qwen2_flops_by_hand():
    cfg = cfg_named("qwen2-0.5b")
    # per layer: q 896x896, k and v 896x128 each, o 896x896,
    # gate/up/down 3 x 896x4864; 24 layers; tied head 151936 x 896
    per_layer = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864
    assert flops.layer_matmul_params(cfg) == 24 * per_layer == 357_826_560
    assert flops.head_params(cfg) == 151_936 * 896 == 136_134_656
    # attention at the causal mean span 256.5: 4 x 14 x 64 x 256.5 x 24
    fwd = 2 * (357_826_560 + 136_134_656) + 4 * 14 * 64 * 256.5 * 24
    assert flops.train_flops_per_token(cfg, 512) == pytest.approx(3 * fwd)
    assert flops.train_flops_per_token(cfg, 512) == pytest.approx(3.030e9,
                                                                 rel=1e-3)


def test_starcoder2_flops_by_hand():
    cfg = cfg_named("starcoder2-3b")
    # q and o 3072x3072, k and v 3072x256, up and down 3072x12288
    per_layer = 3072 * 3072 * 2 + 3072 * 256 * 2 + 2 * 3072 * 12288
    assert flops.layer_matmul_params(cfg) == 30 * per_layer
    assert flops.head_params(cfg) == 49_152 * 3072
    # one decoded token at position 99 attends to 100 keys
    want = 2 * (30 * per_layer + 49_152 * 3072) + 4 * 24 * 128 * 100 * 30
    assert flops.decode_flops(cfg, 99) == pytest.approx(want)
    assert 2 * (30 * per_layer + 49_152 * 3072) == pytest.approx(6.056e9,
                                                                rel=1e-3)


def test_tied_head_is_counted():
    cfg = cfg_named("qwen2-0.5b")
    assert cfg["tie_word_embeddings"]
    untied = dict(cfg, tie_word_embeddings=False)
    assert flops.decode_flops(cfg, 0) == flops.decode_flops(untied, 0)
    assert flops.decode_flops(cfg, 0) > 2 * flops.layer_matmul_params(cfg)


def test_request_flops_sums_prefill_and_decodes():
    cfg = cfg_named("qwen2-0.5b")
    p, n = 40, 5
    want = flops.prefill_flops(cfg, p) + sum(
        flops.decode_flops(cfg, p + j) for j in range(n - 1))
    assert flops.request_flops(cfg, p, n) == pytest.approx(want)
    # the first token comes from the prefill alone
    assert flops.request_flops(cfg, p, 1) == flops.prefill_flops(cfg, p)


# -- peaks ------------------------------------------------------------------

def test_peaks_known_device():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_peaks_refuse_unknown_device(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(kind)


# -- traffic ----------------------------------------------------------------

def test_train_batches_repeat_for_a_seed_and_differ_across():
    mix = harness.traffic_of("train.seq512")
    a = traffic.train_batch(mix, 1000, 2**33 + 1, 4, 8)
    b = traffic.train_batch(mix, 1000, 2**33 + 1, 4, 8)
    c = traffic.train_batch(mix, 1000, 2**33 + 2, 4, 8)
    d = traffic.train_batch(mix, 1000, 2**33 + 1, 5, 8)
    assert a["tokens"].shape == (8, 512) and a["tokens"].dtype == "int32"
    assert (a["tokens"] == b["tokens"]).all()
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()
    assert (a["tokens"] != c["tokens"]).any()
    assert (a["tokens"] != d["tokens"]).any()
    assert 0 <= a["tokens"].min() and a["tokens"].max() < 1000
    # every row differs
    assert len({r.tobytes() for r in a["tokens"]}) == 8


def _sig(reqs):
    return [(p.tobytes(), n) for p, n in reqs]


def test_chat_rounds_repeat_for_a_seed_and_differ_across():
    mix = harness.traffic_of("chat.conv.c32")
    a = traffic.round_requests(mix, 49_152, 123_456_789_012, 3)
    assert _sig(a) == _sig(traffic.round_requests(mix, 49_152,
                                                   123_456_789_012, 3))
    assert _sig(a) != _sig(traffic.round_requests(mix, 49_152,
                                                   123_456_789_013, 3))
    assert _sig(a) != _sig(traffic.round_requests(mix, 49_152,
                                                   123_456_789_012, 4))


def test_chat_rounds_hold_the_same_sizes_in_another_order():
    mix = harness.traffic_of("chat.conv.c32")
    loops = mix["serve"]["event_loops"]
    sizes = lambda r: sorted((len(p), n) for p, n in r)
    one = traffic.round_requests(mix, 1000, 5, 0)
    other = traffic.round_requests(mix, 1000, 6, 0)
    assert len(one) == mix["clients"]
    assert sizes(one) != sizes(other)         # pairing differs by seed
    # every loop (client c goes to loop c % loops) gets the same sizes
    for l in range(loops):
        a, b = one[l::loops], other[l::loops]
        assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
        assert sorted(n for _, n in a) == sorted(n for _, n in b)
    assert max(len(p) + n for p, n in one) <= mix["serve"]["max_len"]


def test_chat_quantiles_follow_the_lognormal():
    mix = harness.traffic_of("chat.conv.c32")
    n = mix["clients"]
    prompts = traffic.quantiles(mix["prompt"], n)
    outputs = traffic.quantiles(mix["output"], n)
    assert prompts == sorted(prompts) and outputs == sorted(outputs)
    assert statistics.median(prompts) == pytest.approx(1020, rel=0.05)
    assert statistics.median(outputs) == pytest.approx(129, rel=0.05)
    assert max(outputs) == mix["output"]["max"]
    assert max(prompts) == mix["prompt"]["max"]
    assert min(outputs) >= mix["output"]["min"]
    dealt = traffic.loop_sizes(mix)
    assert sorted(x for p, _ in dealt for x in p) == prompts
    assert sorted(x for _, o in dealt for x in o) == outputs


def test_warmup_covers_every_prompt_length():
    mix = harness.traffic_of("chat.conv.c32")
    warm = traffic.warmup_requests(mix, 1000, 9)
    lengths = [len(r[0][0]) for r in warm]
    loops = mix["serve"]["event_loops"]
    # a wave pads its prompts to its longest: one prefill shape per loop
    rounds = [traffic.round_requests(mix, 1000, s, r)
              for s in (9, 10) for r in (0, 1)]
    waves = {max(len(p) for p, _ in reqs[l::loops])
             for reqs in rounds for l in range(loops)}
    assert sorted(lengths) == sorted(waves)
    assert all(len(r) == mix["clients"] for r in warm)
    assert _sig(warm[0]) != _sig(traffic.round_requests(mix, 1000, 9, 0))


# -- configuration files ----------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_config_resolves_to_repo_widths(name):
    cfg = cfg_named(name)
    mc = model.program_config(cfg)
    assert (mc.num_layers, mc.d_model, mc.num_heads, mc.num_kv_heads,
            mc.d_ff, mc.vocab_size) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["vocab_size"])
    assert mc.param_dtype == cfg["torch_dtype"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_tree_is_the_programs(name):
    from repro.models import api
    cfg = cfg_named(name)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        api.abstract(model.program_config(cfg)))
    got = jax.tree.map(lambda s: (s, cfg["torch_dtype"]),
                       model.weight_shapes(cfg),
                       is_leaf=lambda x: isinstance(x, tuple))
    assert got == want


@pytest.mark.parametrize("name", CONFIGS)
def test_reduced_names_what_differs_from_published(name):
    cfg = cfg_named(name)
    for entry in BENCH["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == cfg["reduced"]
            assert harness.load_json(harness.ROOT / entry["file"]) == cfg
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    for key, value in cfg["published"].items():
        assert cfg[key] != value
    widths = re.compile(r"(hidden|intermediate|head|_dim$|_rank$|size)")
    assert not [k for k in cfg["reduced"] if widths.search(k)]


def test_weights_repeat_for_a_seed():
    cfg = cells.shrink(cfg_named("starcoder2-3b"), num_hidden_layers=1,
                       hidden_size=32, num_attention_heads=4,
                       num_key_value_heads=2, intermediate_size=64,
                       vocab_size=64)
    a = model.init_weights(cfg, 2**35 + 3)
    b = model.init_weights(cfg, 2**35 + 3)
    c = model.init_weights(cfg, 2**35 + 4)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert (a["layers"]["attn"]["wq"] != c["layers"]["attn"]["wq"]).any()
    assert a["layers"]["mlp"]["bi"].std() > 0        # biases carry signal


# -- BENCHMARK.json ---------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_file_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert harness.end_to_end_for(BENCH, w["name"])
        assert harness.per_layer_for(BENCH, w["name"])
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        harness.config_of(BENCH, w["config"])
        assert harness.traffic_of(w["traffic"])["driver"]
        assert harness.limits_of(w["name"])["checks"]
    for m in BENCH["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_check_lines_put_checks_last(capsys):
    res = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
           "device": {}, "checks": harness.checks(
               {"a": 0.5, "b": float("nan")},
               {"checks": {"a": {"limit": 1.0}, "b": {"limit": 1.0}}})}
    assert not harness.passed(res["checks"])
    harness.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["checks"]["b"]["value"] is None
    assert err.strip().splitlines()[-1].startswith("check b:")


READINGS = {"train": {"loss_gap", "grad_norm_gap", "update_norm_gap"},
            "serve_closed": {"logit_gap"}}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_limits_sit_between_their_readings(cell):
    w = harness.cell_of(BENCH, cell)
    driver = harness.traffic_of(w["traffic"])["driver"]
    checks = harness.limits_of(cell)["checks"]
    assert set(checks) == READINGS[driver]
    for c in checks.values():
        assert c["lower"] < c["limit"] < c["upper"]
