"""A configuration states its RuleSets and its sidecar arguments, and the
harness deploys what it states (PR 32): the argv and the cache server's
content of the configurations that were there are what they were; a
configuration with ``instances`` and ``sidecar_args`` gets both; the
repeat generator sends a plan's ``prime`` groups where it has them and
the whole pool where not; the two cells the PR adds resolve. JAX-free
and fast but for the last two tests, which start sidecars on the CPU
(slow, like ``test_served_path.py``).
"""

import hashlib
import json
import shutil
import time
from pathlib import Path

import pytest

from wafbench import harness
from wafbench.generators.planned_bursts import SALT_TOKEN

BENCH = Path(harness.__file__).resolve().parent
SAMPLE = BENCH / "configs" / "operator-sample"

# Taken from the parent commit (PR 31's tree) before the harness changed:
# the one argv it built, and SHA-256 of each configuration's rule text
# (its own and its control's) with the checkout's path written <config>.
GOLDEN_ARGV = ["--cache-server-instance", "wafbench/ruleset",
               "--cache-server-cluster", "127.0.0.1:<cache port>",
               "--bind-address", "127.0.0.1",
               "--compile-cache-dir", "<checkout>/build/wafbench/jax_cache",
               "--port", "<port>"]
GOLDEN_TEXT = {
    "crs-lite-pl2": ("4b4825d28fa758ad3f342461cddb6e8a2a4766d2832414053b2c867a0d442579",
                     "314810e27d48181e4f00c7dd752bea723eabb9a39f647f194b66cea3c221a49c"),
    "operator-sample": ("38f55b4156ee875d8c9926f76722d06f5454a82d292b36d968d1f3582a33d983",
                        "4723ab014a5fc5185c6730eb53a4d7ae20ada698ba9cbf8185b47434f5ab33bc"),
    "crs-lite-pl2-bodies": ("4b4825d28fa758ad3f342461cddb6e8a2a4766d2832414053b2c867a0d442579",
                            "110849b826f0f89cfc97850d3b6b6288a844ec41cae54c5e367f4aaff707ad7d"),
}
CELL_OF = {"crs-lite-pl2": "crs-lite.ftw-salted-c1", "operator-sample": "sample.salted-c2",
           "crs-lite-pl2-bodies": "crs-bodies.api-2k-c1"}
# One pass of sample.zipf-c2 (prime, then every group of both connections
# once) at the parent commit, by seed.
GOLDEN_ZIPF = {5: "7e22bc2c30ab133a7276a3d63add1da34de0447e900a52f2561fedf52f84fb6c",
               2**31 + 5: "9361605b2ba36be797211fe133529d9170859571a8865b4bf02b110fdc911052"}


def checkout_with(tmp_path, name: str, config_edit: dict, rules: dict[str, str]) -> Path:
    """A scratch checkout: the benchmark's files, and the sample
    configuration copied to ``configs/<name>`` with ``config_edit`` laid
    over its ``config.json`` and ``rules`` (file name -> text) beside it;
    one cell ``<name>.salted-c2`` on the sample's own traffic."""
    root = tmp_path / "checkout"
    for d in ("generators", "layer_metrics", "traffic"):
        shutil.copytree(BENCH / d, root / "wafbench" / d)
    cdir = root / "wafbench" / "configs" / name
    shutil.copytree(SAMPLE, cdir)
    config = json.loads((cdir / "config.json").read_text())
    config.update(config_edit)
    (cdir / "config.json").write_text(json.dumps(config))
    for fname, text in rules.items():
        (cdir / fname).write_text(text)
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                             "file": f"wafbench/configs/{name}/config.json"})
    bench["workloads"].append({"name": f"{name}.salted-c2", "config": name,
                               "traffic": "salted-c2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "sample.salted-c2" in m.get("workloads", []):
            m["workloads"].append(f"{name}.salted-c2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def two_instances(tmp_path, second_text: str | None = None, **more) -> harness.Cell:
    sample = (SAMPLE / "rules.conf").read_text()
    root = checkout_with(
        tmp_path, "two-tenants",
        {"instances": [{"instance": "tenant-a/ruleset", "rules": "rules.conf"},
                       {"instance": "tenant-b/ruleset", "rules": "b.conf"}], **more},
        {"b.conf": sample if second_text is None else second_text})
    return harness.Cell("two-tenants.salted-c2", root=root)


@pytest.mark.parametrize("config", sorted(GOLDEN_TEXT))
def test_existing_configurations_deploy_what_they_did(config):
    cell = harness.Cell(CELL_OF[config])
    assert "instances" not in cell.config and "sidecar_args" not in cell.config
    argv = cell.sidecar_argv(41001, 41002, harness.WORK / "jax_cache")
    assert argv == [a.replace("<cache port>", "41001").replace("<port>", "41002")
                    .replace("<checkout>", str(harness.REPO)) for a in GOLDEN_ARGV]
    # JAX_COMPILATION_CACHE_DIR set: the harness names no directory, as before
    assert cell.sidecar_argv(41001, 41002, None) == argv[:6] + argv[8:]
    for control, want in zip((False, True), GOLDEN_TEXT[config]):
        texts = cell.rules_texts(control=control)
        assert list(texts) == [harness.INSTANCE]  # one put, under the one name
        text = texts[harness.INSTANCE]
        assert text == cell.rules_text(control=control)
        plain = text.replace(str(cell.config_dir.resolve()), "<config>")
        assert hashlib.sha256(plain.encode()).hexdigest() == want


def test_two_instances_and_sidecar_args_are_deployed_in_order(tmp_path):
    extra = ["--max-batch-delay-ms", "2", "--extproc-port=0"]
    cell = two_instances(tmp_path, second_text="SecRuleEngine On\n", sidecar_args=extra)
    argv = cell.sidecar_argv(1, 2, None)
    assert argv[:2] == ["--cache-server-instance", "tenant-a/ruleset,tenant-b/ruleset"]
    assert argv[-len(extra):] == extra and argv.index("--port") < len(argv) - len(extra)
    texts = cell.rules_texts()
    assert list(texts) == ["tenant-a/ruleset", "tenant-b/ruleset"]  # the default tenant first
    assert texts["tenant-a/ruleset"] == (SAMPLE / "rules.conf").read_text()
    assert texts["tenant-b/ruleset"] == "SecRuleEngine On\n"
    assert cell.rules_text() == texts["tenant-a/ruleset"]
    # what /waf/v1/stats has to show before the run goes on
    loaded = {"tenants": {"tenant-a/ruleset": {"loaded": True}, "tenant-b/ruleset": {"loaded": False}}}
    assert cell.not_loaded(loaded) == ["tenant-b/ruleset"]
    assert cell.not_loaded({"tenants": {}}) == ["tenant-a/ruleset", "tenant-b/ruleset"]
    loaded["tenants"]["tenant-b/ruleset"]["loaded"] = True
    assert cell.not_loaded(loaded) == []


@pytest.mark.parametrize("arg", [*harness.HARNESS_FLAGS, "--port=9", "--cache-server-inst",
                                 "--compile-cache-dir=/tmp/x"])
def test_a_flag_the_harness_sets_is_refused(tmp_path, arg, capsys, monkeypatch):
    cell = two_instances(tmp_path, sidecar_args=["--max-batch-size", "64", arg, "x"])
    with pytest.raises(harness.RunFailure) as refused:
        cell.sidecar_args()
    assert refused.value.phase == "sidecar_args" and arg in refused.value.why
    # and a whole run says so on a phase line before it starts anything
    monkeypatch.setattr(harness, "Cell", lambda workload: cell)

    def started(*a, **kw):
        raise AssertionError(f"the harness started {a[0]}")

    monkeypatch.setattr(harness.subprocess, "call", started)  # the native build
    monkeypatch.setattr(harness.subprocess, "Popen", started)  # the sidecar
    rc, result = harness.run_cell("two-tenants.salted-c2", 1, 1.0, False, time.monotonic())
    assert rc != 0 and result is None
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "sidecar_args" and line["ok"] is False


def test_a_flag_of_the_configurations_own_is_not_refused(tmp_path):
    cell = two_instances(tmp_path, sidecar_args=["--pipeline-depth", "2", "-p", "--", "--x=--port"])
    assert cell.sidecar_args() == ["--pipeline-depth", "2", "-p", "--", "--x=--port"]


def test_control_edits_the_instance_it_names_and_no_other(tmp_path):
    sample = (SAMPLE / "rules.conf").read_text()
    control = json.loads((SAMPLE / "config.json").read_text())["control"]
    by_default = two_instances(tmp_path / "a").rules_texts(control=True)
    assert by_default["tenant-b/ruleset"] == sample  # the first is edited where none is named
    assert by_default["tenant-a/ruleset"] != sample and "id:941100,phase:2,pass," in by_default["tenant-a/ruleset"]
    named = two_instances(tmp_path / "b", control={**control, "instance": "tenant-b/ruleset"})
    texts = named.rules_texts(control=True)
    assert texts["tenant-a/ruleset"] == sample
    assert texts["tenant-b/ruleset"] == by_default["tenant-a/ruleset"]
    assert named.rules_texts() == {"tenant-a/ruleset": sample, "tenant-b/ruleset": sample}
    unknown = two_instances(tmp_path / "c", control={**control, "instance": "tenant-c/ruleset"})
    with pytest.raises(SystemExit):
        unknown.rules_texts(control=True)


def first_pass(traffic) -> str:
    h = hashlib.sha256()
    for burst in traffic.prime:
        h.update(traffic.salted(burst, "prime"))
    for conn in range(len(traffic.connections)):
        stream = traffic.stream(conn)
        for _ in range(len(traffic.connections[conn])):
            h.update(traffic.salted(next(stream), f"c{conn}"))
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(GOLDEN_ZIPF))
def test_the_repeat_cell_that_was_there_sends_the_bytes_it_sent(seed):
    cell = harness.Cell("sample.zipf-c2")
    plan = json.loads((cell.config_dir / "plans" / "zipf.json").read_text())
    assert "prime" not in plan
    traffic = cell.traffic(seed)
    assert [b.n for b in traffic.prime] == [128, 128]  # no prime list: each pool whole
    assert first_pass(traffic) == GOLDEN_ZIPF[seed]


def test_a_plans_prime_groups_are_sent_in_place_of_the_whole_pool(tmp_path):
    cdir = tmp_path / "cfg"
    shutil.copytree(SAMPLE, cdir)
    plan = json.loads((cdir / "plans" / "zipf.json").read_text())
    pool = plan["repeat"]["interactive"]
    plan["prime"] = [{"lane": "interactive", "requests": pool[:5]},
                     {"lane": "interactive", "requests": pool[5:] + [plan["steady"][0]["requests"][0]]},
                     {"lane": "bulk", "requests": plan["repeat"]["bulk"]}]
    (cdir / "plans" / "grouped.json").write_text(json.dumps(plan))
    mix = dict(harness.Cell("sample.zipf-c2").mix, plan="grouped")
    gen = harness.load_by_path(BENCH / "generators" / "zipf_repeat.py")
    grouped, whole = gen.Traffic(cdir, mix, 7), gen.Traffic(cdir, dict(mix, plan="zipf"), 7)
    assert [(b.lane, b.n) for b in grouped.prime] == [
        ("interactive", 5), ("interactive", 124), ("bulk", 128)]
    # the same fixed bytes as the whole pool, so every later draw is a repeat
    fixed = lambda t: [p[0] for b in t.prime for p in b.parts]
    assert all(len(p) == 1 for b in grouped.prime for p in b.parts)
    assert set(fixed(whole)) < set(fixed(grouped)) and len(fixed(grouped)) == 257
    assert grouped.salted(grouped.prime[0], "prime") == grouped.salted(grouped.prime[0], "prime")
    assert [b.n for b in whole.prime] == [128, 128]


@pytest.mark.parametrize("workload", ["crs-lite.ftw-repeat80-c1", "crs-lite.ftw-salted-c2"])
def test_the_new_cells_resolve(workload):
    cell = harness.Cell(workload)
    assert cell.config["name"] == "crs-lite-pl2" and cell.workload["chips"] == 1
    salted = harness.Cell("crs-lite.ftw-salted-c1")
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]).read), m["name"]
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"assemble_ms_per_window", "prefilter_wait_ms_per_window", "dispatch_wait_ms_per_window",
            "matcher_device_ms_per_window", "device_idle_share"} <= names
    assert not names & {"batcher_host_stage_p50_ms", "native_window_p50_ms",
                        "long_tier_device_ms_per_window", "tier_padding_share"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        m["name"] for m in salted.metrics("end_to_end")}
    traffic = cell.traffic(2**31 + 32)
    no_cache = [k for k in salted.mix["zero_growth"] if not k.startswith("verdict_cache.")]
    if workload.endswith("salted-c2"):
        assert cell.mix["zero_growth"] == salted.mix["zero_growth"]
        assert "verdict_cache_hit_share" not in names
        assert [len(c) for c in traffic.connections] == [40, 6]  # one lane a connection
        assert {b.lane for b in traffic.connections[0]} == {"interactive"}
        assert {b.lane for b in traffic.connections[1]} == {"bulk"}
        one = salted.traffic(2**31 + 32)
        key = lambda b: (b.lane, tuple(map(tuple, b.parts)))
        assert sorted(map(key, traffic.connections[0] + traffic.connections[1])) == sorted(
            map(key, one.connections[0]))  # cell 1's bursts, split by lane
        return
    assert cell.mix["zero_growth"] == no_cache and "verdict_cache_hit_share" in names
    plan = json.loads((cell.config_dir / "plans" / "ftw-repeat80.json").read_text())
    source = json.loads((cell.config_dir / "plans" / "ftw-salted.json").read_text())
    assert [g["requests"] for g in plan["steady"]] == [g["requests"] for g in source["steady"]]
    assert {len(v) for v in plan["repeat"].values()} == {48, 31}
    # no window of the prime pass or of the loop leaves the one matcher shape
    assert all(g["tier_shapes"] == [[32, 512]] for g in plan["prime"] + plan["steady"])
    assert all(17 <= g["unique_uncached_rows"] <= 30 for g in plan["prime"])
    assert {g["unique_uncached_rows"] for g in plan["steady"]} == {24}
    primed = {i for g in plan["prime"] for i in g["requests"]}
    assert primed >= {i for v in plan["repeat"].values() for i in v}
    assert primed >= {i for g in plan["steady"] for i in g["requests"]}  # no first sight in the loop
    assert len(traffic.prime) == len(plan["prime"]) and len(traffic.connections) == 1
    fixed = {p[0] for b in traffic.prime for p in b.parts}
    assert not any(SALT_TOKEN in w for w in fixed)
    stream = traffic.stream(0)
    for _ in range(60):
        burst = next(stream)
        unsalted = [p[0] for p in burst.parts if len(p) == 1]
        assert burst.n == 30 and len(unsalted) == 24 and set(unsalted) <= fixed


# -- whole runs on the CPU (slow: each starts a sidecar) -----------------------------


def run_on_the_cpu(monkeypatch, cell: harness.Cell, **kw):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(harness, "Cell", lambda workload: cell)
    rc, result = harness.run_cell(cell.workload["name"], seed=2**31 + 32, seconds=3.0, trace=False,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False, **kw)
    assert rc == 0
    return result


def test_two_instances_load_and_the_default_answers(tmp_path, monkeypatch, capsys):
    result = run_on_the_cpu(monkeypatch, two_instances(tmp_path, sidecar_args=["--pipeline-depth", "2"]))
    assert result["correct"] is True and result["failed_checks"] == []
    assert result["compared"]["instances_not_loaded"] == {"value": 0, "limit": 0}
    assert list(result)[-1] == "compared"
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    ready = next(ln for ln in lines if ln.get("phase") == "ready")
    assert ready["instances"] == ["tenant-a/ruleset", "tenant-b/ruleset"]
    assert ready["sidecar_args"] == ["--pipeline-depth", "2"]


def test_an_instance_that_does_not_load_is_not_correct(tmp_path, monkeypatch):
    broken = two_instances(tmp_path, second_text='SecRule ARGS "@rx (" "id:1,phase:2,deny"\n')
    result = run_on_the_cpu(monkeypatch, broken)
    assert result["correct"] is False
    assert result["failed_checks"] == ["instances_not_loaded"]
    assert result["compared"]["instances_not_loaded"] == {"value": 1, "limit": 0}
