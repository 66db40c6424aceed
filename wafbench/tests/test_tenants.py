"""The 32-tenant deployment (PR 33): what the configuration states is
what is deployed; its data regenerate byte for byte from
``freeze_tenants``; every burst of the plan has the one composition; a
rehearsal on the CPU ends with every check 0 but the device's, and the
control and a copy that does not trust the tenant header are not
correct. Slow but for the first three (an engine per rule text, whole
runs): run with ``pytest wafbench/tests``; not part of tier-1.
"""

import base64
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

from wafbench import harness

CELL = "tenants32.zipf-salted-c2"
BENCH = Path(harness.__file__).resolve().parent
CDIR = BENCH / "configs" / "operator-sample-tenants32"
BASE = (BENCH / "configs" / "operator-sample" / "rules.conf").read_text()
COMPOSITION = {"a": 52, "b": 32, "c": 24, "d": 20}
# SHA-256 of the four rule texts as this PR froze them.
FROZEN_TEXT = {
    "a": "38f55b4156ee875d8c9926f76722d06f5454a82d292b36d968d1f3582a33d983",
    "b": "a6cea90ca669c9ccd43b0ab33e6ee45ad7d4d76732218cfd79e62fada07a71b7",
    "c": "17031b18b560d79211f21d3f3101dacc42acb7257314029777ce162394233f2c",
    "d": "761f7fc8c1dd8b5afeaad34f741c88752b161743d26ff344b3cf85d542e500bb",
}


def corpus() -> list[dict]:
    with open(CDIR / "corpus.jsonl") as fh:
        return [json.loads(line) for line in fh]


def test_the_configuration_deploys_32_instances_over_4_texts_and_trusts_the_header():
    cell = harness.Cell(CELL)
    assert cell.workload == {**cell.workload, "config": "operator-sample-tenants32",
                             "traffic": "tenants-zipf-salted-c2", "chips": 1}
    assert cell.config["architecture"] is None and len(cell.config["reduced"]) == 2
    assert "isolation" in cell.config["guarantees"]
    argv = cell.sidecar_argv(41001, 41002, harness.WORK / "jax_cache")
    names = [f"tenant-{r:02d}/ruleset" for r in range(32)]
    assert argv == ["--cache-server-instance", ",".join(names),
                    "--cache-server-cluster", "127.0.0.1:41001", "--bind-address", "127.0.0.1",
                    "--compile-cache-dir", str(harness.WORK / "jax_cache"),
                    "--port", "41002", "--trust-tenant-header"]
    texts = cell.rules_texts()
    assert list(texts) == names  # the default tenant first
    by_text: dict[str, list[int]] = {}
    for rank, name in enumerate(names):
        by_text.setdefault(texts[name], []).append(rank)
    assert [ranks for ranks in by_text.values()] == [list(range(t, 32, 4)) for t in range(4)]
    for letter, text in zip("abcd", by_text):
        assert text.startswith(BASE)  # the sample's base, byte for byte, then the tenant's own
        assert text == (CDIR / "rules" / f"text-{letter}.conf").read_text()
        assert hashlib.sha256(text.encode()).hexdigest() == FROZEN_TEXT[letter]
    assert list(by_text)[0] == BASE
    # the control edits the default tenant's text and no other
    control = cell.rules_texts(control=True)
    assert [n for n in names if control[n] != texts[n]] == ["tenant-00/ruleset"]
    assert "id:941100,phase:2,pass," in control["tenant-00/ruleset"]
    # the mix is sample.salted-c2's but for the plan
    salted = harness.Cell("sample.salted-c2").mix
    assert {**cell.mix, "plan": "salted"} == salted
    names_read = {m["name"] for m in cell.metrics("per_layer")}
    assert {"tenant_blob_path_share", "engine_windows_per_read", "ingest_parse_us_per_req",
            "window_requests_mean", "host_answered_share", "assemble_ms_per_window",
            "matcher_load_s", "matcher_device_ms_per_window", "device_idle_share"} <= names_read
    assert {m["name"] for m in harness.Cell("sample.salted-c2").metrics("per_layer")} \
        .isdisjoint({"tenant_blob_path_share", "engine_windows_per_read"})


def test_every_burst_has_the_one_composition_and_lands_on_the_plans_shapes():
    pool = corpus()
    plan = json.loads((CDIR / "plans" / "tenants-salted.json").read_text())
    frozen = json.loads((CDIR / "frozen.json").read_text())
    assert plan["composition"] == COMPOSITION and len(plan["steady"]) == 33
    assert {i for b in plan["steady"] for i in b["requests"]} == set(range(4096))
    assert [b["requests"] for b in plan["prime"]] == [b["requests"] for b in plan["steady"]]
    sample = [json.loads(line) for line in open(BENCH / "configs" / "operator-sample" / "corpus.jsonl")]
    for mine, theirs in zip(pool, sample, strict=True):
        wire = base64.b64decode(mine["wire"])
        header = f"X-Waf-Tenant: {mine['tenant']}\r\n".encode()
        assert wire.count(header) == 1 and wire.replace(header, b"") == base64.b64decode(theirs["wire"])
        rank = int(mine["tenant"][7:9])
        assert mine["text"] == "abcd"[rank % 4]
    for b in plan["steady"] + plan["prime"]:
        assert len(b["requests"]) == 128
        assert Counter(pool[i]["text"] for i in b["requests"]) == COMPOSITION
        assert len({pool[i]["tenant"] for i in b["requests"]}) == b["tenants"] >= 12
        heads = {base64.b64decode(pool[i]["wire"]).split(b" ", 1)[0] for i in b["requests"]}
        assert heads == ({b"POST"} if b["lane"] == "bulk" else {b"GET"})  # one lane a burst
        assert {k: w["requests"] for k, w in b["windows"].items()} == COMPOSITION
    for b in plan["steady"]:
        for letter, w in b["windows"].items():
            assert w["tier_shapes"] in plan["tier_shapes"][b["lane"]][letter]
    # rank 1 takes about 28% of a pass, and the verdicts that depend on the tenant are there
    per_pass = Counter(pool[i]["tenant"] for b in plan["steady"] for i in b["requests"])
    assert 0.27 < per_pass["tenant-00/ruleset"] / plan["requests_per_pass"] < 0.30
    assert frozen["verdict_depends_on_tenant"] >= 64
    assert all(frozen["verdict_depends_on_tenant_by_text"][k] > 0 for k in "bcd")
    traffic = harness.Cell(CELL).traffic(2**31 + 33)
    assert [len(c) for c in traffic.connections] == [23, 10] and len(traffic.prime) == 33


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    lib = tmp_path_factory.mktemp("native") / "libcko_native.so"
    subprocess.check_call(["make", "-C", str(harness.REPO / "native"), f"TARGET={lib}"],
                          stdout=subprocess.DEVNULL)
    return lib


def test_the_data_regenerate_byte_for_byte(tmp_path, native_lib):
    configs = tmp_path / "configs"
    shutil.copytree(BENCH / "configs" / "operator-sample", configs / "operator-sample")
    copy = configs / CDIR.name
    shutil.copytree(CDIR, copy)
    for made in ("corpus.jsonl", "frozen.json", "plans/tenants-salted.json"):
        (copy / made).unlink()
    subprocess.run(
        [sys.executable, "-m", "wafbench.tools.freeze_tenants", str(copy)], cwd=harness.REPO,
        check=True, capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", CKO_NATIVE_LIB=str(native_lib)))
    for made in ("corpus.jsonl", "frozen.json", "plans/tenants-salted.json"):
        assert (copy / made).read_bytes() == (CDIR / made).read_bytes(), made


# -- whole runs on the CPU (slow: each starts a sidecar with 32 instances) ---------------


def run_on_the_cpu(monkeypatch, cell=None, **kw):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    if cell is not None:
        monkeypatch.setattr(harness, "Cell", lambda workload: cell)
    rc, result = harness.run_cell(CELL, seed=2**31 + 33, seconds=3.0, trace=False,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False, **kw)
    assert rc == 0
    return result


def test_a_rehearsal_is_correct_and_the_control_is_not(monkeypatch):
    sound = run_on_the_cpu(monkeypatch)
    assert sound["correct"] is True and sound["failed_checks"] == []
    assert all(c["value"] == 0 for c in sound["compared"].values())
    assert sound["compared"]["instances_not_loaded"] == {"value": 0, "limit": 0}
    control = run_on_the_cpu(monkeypatch, control=True)
    assert control["correct"] is False
    assert control["failed_checks"] == ["verdicts_that_differ"]


def test_a_copy_that_does_not_trust_the_header_is_not_correct(tmp_path, monkeypatch):
    """Every request is then judged by the first instance's text: the
    verdicts that depend on the tenant differ, and nothing else fails."""
    root = tmp_path / "checkout"
    for d in ("generators", "layer_metrics", "traffic"):
        shutil.copytree(BENCH / d, root / "wafbench" / d)
    shutil.copytree(CDIR, root / "wafbench" / "configs" / CDIR.name)
    cfg = root / "wafbench" / "configs" / CDIR.name / "config.json"
    config = json.loads(cfg.read_text())
    del config["sidecar_args"]
    cfg.write_text(json.dumps(config))
    shutil.copy(harness.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    result = run_on_the_cpu(monkeypatch, harness.Cell(CELL, root=root))
    assert result["correct"] is False
    assert result["failed_checks"] == ["verdicts_that_differ"]
    assert result["compared"]["verdicts_that_differ"]["value"] > 0
