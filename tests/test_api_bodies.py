"""Bodied API requests (wafbench's ``crs-lite-pl2-bodies``) at a small
size on the CPU: the native body processors against their Python twins,
the device against the host evaluator on windows that hold short and
long rows together, and the tiering of such windows.

The native library is ``conftest.py``'s ``native_lib``, built from the
committed source, so these cases run in a checkout where nobody ran
``make native``. Full crs-lite does not
compile on XLA:CPU, so the verdict cases ride ``ftw/rules/crs-mini.conf``
on ``base.conf`` (body access on, as the deployment has it).
"""

import base64
import json
import random
import time
from pathlib import Path

import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest
from coraza_kubernetes_operator_tpu.engine import waf as waf_mod
from coraza_kubernetes_operator_tpu.engine.waf import _bucket_rows, tier_tensors
from coraza_kubernetes_operator_tpu.native import serialize_requests

from conftest import native_engine
from test_native_tiered import _assert_window_parity as _tiered_parity

REPO = Path(__file__).resolve().parents[1]
BODIES = REPO / "wafbench" / "configs" / "crs-lite-pl2-bodies"
RULES = (REPO / "ftw/rules/base.conf").read_text() + "\n" + (
    REPO / "ftw/rules/crs-mini.conf").read_text()
SALT_TOKEN = b"__WAFBENCH_SALT__"


@pytest.fixture(scope="module")
def engine(native_lib):
    eng = native_engine(RULES, native_lib)
    assert eng._native.tiered
    return eng


@pytest.fixture(scope="module")
def python_engine():
    return native_engine(RULES, None)


def post(ctype: str, body: bytes, uri: str = "/api/v1/orders") -> HttpRequest:
    return HttpRequest(
        method="POST", uri=uri, body=body,
        headers=[("Host", "api.bench.local"), ("User-Agent", "okhttp/4.12.0"),
                 ("Accept", "application/json"), ("Content-Type", ctype),
                 ("Content-Length", str(len(body)))])


# -- (a) body parity: native tensorizer == Python tensorizer ---------------------


def _json_value(rng: random.Random, depth: int):
    r = rng.random()
    if depth < 3 and r < 0.2:
        return {rng.choice("abcdefgh") * rng.randrange(1, 4): _json_value(rng, depth + 1)
                for _ in range(rng.randrange(0, 4))}
    if depth < 3 and r < 0.4:
        return [_json_value(rng, depth + 1) for _ in range(rng.randrange(0, 4))]
    if r < 0.5:
        return rng.randrange(-10**6, 10**9)
    if r < 0.55:
        return rng.choice([True, False, None])
    alphabet = "abc xyz0189 ,;<>'\"\\/\n\t%+&=éü中\U0001f600"
    return "".join(rng.choices(alphabet, k=rng.randrange(0, 40)))


def _json_bodies(rng):
    for _ in range(12):
        doc = {"nonce": "%032x" % rng.getrandbits(128), "v": _json_value(rng, 0)}
        yield "application/json", json.dumps(
            doc, ensure_ascii=rng.random() < 0.7,
            separators=rng.choice(((",", ":"), (", ", ": ")))).encode()


def _invalid_json_bodies(rng):
    for ctype, body in _json_bodies(rng):
        cut = rng.randrange(1, len(body))
        yield ctype, rng.choice([
            body[:cut], body[:cut] + b"}" * 3, body.replace(b":", b"=", 1),
            body + b",", b"[" * 40 + body, body[:cut] + bytes([rng.randrange(256)]) + body[cut:],
            b"", b"nul", b'{"a":1,}', b'{"a" 1}', b"\xff\xfe{}",
        ])


def _form_bodies(rng):
    alphabet = "abcXYZ019 %+&=;<>'\"é\x00/\\"
    for _ in range(12):
        pairs = []
        for _ in range(rng.randrange(1, 9)):
            k = "".join(rng.choices(alphabet, k=rng.randrange(0, 8)))
            v = "".join(rng.choices(alphabet, k=rng.randrange(0, 60)))
            if rng.random() < 0.6:
                from urllib.parse import quote_plus

                k, v = quote_plus(k), quote_plus(v)
            pairs.append(f"{k}={v}" if rng.random() < 0.9 else k)
        body = "&".join(pairs).encode("latin-1", "replace")
        if rng.random() < 0.3:  # broken percent-encoding
            body += rng.choice([b"&p=%", b"&p=%4", b"&p=%zz", b"&%u00e9=%u12"])
        yield "application/x-www-form-urlencoded", body


def _multipart_bodies(rng):
    for _ in range(12):
        boundary = "----b%08x" % rng.getrandbits(32)
        out = b""
        for i in range(rng.randrange(1, 5)):
            value = "".join(rng.choices("abc xyz<>'\";=é\r\n-", k=rng.randrange(0, 80)))
            disp = f'form-data; name="f{i}"'
            if rng.random() < 0.2:
                disp += f'; filename="up{i}.txt"'
            out += (f"--{boundary}\r\nContent-Disposition: {disp}\r\n\r\n".encode()
                    + value.encode("utf-8") + b"\r\n")
        out += f"--{boundary}--\r\n".encode()
        r = rng.random()
        if r < 0.15:
            out = out[: rng.randrange(1, len(out))]  # cut short
        elif r < 0.25:
            out = out.replace(b"Content-Disposition", b"Content-Dispositio", 1)
        elif r < 0.35:
            out = out.replace(b"\r\n\r\n", b"\r\n", 1)
        ctype = f"multipart/form-data; boundary={boundary}"
        if rng.random() < 0.1:
            ctype = "multipart/form-data"
        yield ctype, out


BODY_MAKERS = {"json": _json_bodies, "json-invalid": _invalid_json_bodies,
               "urlencoded": _form_bodies, "multipart": _multipart_bodies}


def _assert_window_parity(engine, reqs, cache, tag):
    """``tier_blob`` (native) against extract -> ``_tensorize`` ->
    ``tier_tensors`` (Python) on one window, every tier bit for bit
    (``tests/test_native_tiered.py``'s contract, here with a library
    this file built); returns the window's matcher shapes."""
    _tiered_parity(engine, reqs, cache, tag)
    tiers, *_rest, lease = engine._native.tier_blob(
        serialize_requests(reqs), len(reqs), engine._kind_block_lut, cache)
    shapes = [tuple(t[0].shape) for t in tiers]
    lease.release()
    return shapes


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", sorted(BODY_MAKERS))
def test_body_rows_native_equal_python(engine, kind, seed):
    rng = random.Random(f"{kind}/{seed}")
    reqs = [post(ctype, body) for ctype, body in BODY_MAKERS[kind](rng)]
    before = engine.body_summary()
    _tiered_parity(engine, reqs, None, f"{kind}/{seed}")
    # both tensorizers counted the same bodies, by the same processor
    after = engine.body_summary()
    native_n, python_n = (
        {k: int(v) for k, v in zip(waf_mod.BODY_COUNTERS, side)}
        for side in (engine._native.bodies, engine._bodies))
    assert native_n == python_n
    bodied = sum(1 for r in reqs if r.body)
    assert sum(after[k] - before[k] for k in waf_mod.BODY_COUNTERS[:4]) == 2 * bodied


class _OlderLib:
    """The library as a build from before the body counters exports it."""

    def __init__(self, lib):
        self._real = lib

    def __getattr__(self, name):
        if name in ("cko_plan_bodies", "cko_result_bodies"):
            raise AttributeError(name)
        return getattr(self._real, name)


@pytest.mark.parametrize("library", ["current", "older"])
def test_a_library_that_counts_no_bodies_says_so(engine, library, monkeypatch):
    """An older ``.so`` tensorizes bodies and counts none of them:
    ``bodies.native_uncounted`` is then true, so the zeros are not
    read as "no bodies came"."""
    if library == "older":
        monkeypatch.setattr(engine._native, "_lib", _OlderLib(engine._native._lib))
    before = engine.body_summary()
    tiers, *_rest, lease = engine._native.tier_blob(
        serialize_requests([post("application/json", b'{"a": "b"}')]), 1,
        engine._kind_block_lut, None)
    lease.release()
    after = engine.body_summary()
    assert after["native_uncounted"] is (library == "older")
    assert after["json_total"] - before["json_total"] == (0 if library == "older" else 1)


# -- the frozen pool ---------------------------------------------------------------


def pool():
    from coraza_kubernetes_operator_tpu.sidecar import ingest

    out = []
    for line in open(BODIES / "corpus.jsonl"):
        r = json.loads(line)
        wire = base64.b64decode(r["wire"])

        def build(salt: bytes, wire=wire):
            head, _, body = wire.replace(SALT_TOKEN, salt).partition(b"\r\n\r\n")
            method, target, version, pairs, _sp = ingest._parse_head(head + b"\r\n\r\n")
            return ingest._materialize(
                method, target.decode("latin-1"), version, pairs, body, b"127.0.0.1")

        out.append(build)
    return out


def window(builders, idxs, seed):
    return [builders[i](b"%032x" % random.Random(f"{seed}/{k}").getrandbits(128))
            for k, i in enumerate(idxs)]


def test_pool_is_what_the_configuration_says():
    frozen = json.loads((BODIES / "frozen.json").read_text())
    spec = json.loads((BODIES / "freeze.json").read_text())
    rows = [json.loads(line) for line in open(BODIES / "corpus.jsonl")]
    assert len(rows) == frozen["pool_requests"] == spec["pool_requests"] == 240
    assert 0.15 <= frozen["blocked"] / len(rows) <= 0.25
    lo, hi = spec["body_bytes"]["clip"]
    for r in rows:
        wire = base64.b64decode(r["wire"])
        head, _, body = wire.partition(b"\r\n\r\n")
        assert head.split(b" ", 1)[0] in (b"POST", b"PUT", b"PATCH")
        assert body.count(SALT_TOKEN) == 1 and b"nonce" in body
        sent = len(body) - len(SALT_TOKEN) + spec["salt_hex"]
        assert lo <= sent <= hi
        assert f"Content-Length: {sent}\r\n".encode() in head + b"\r\n"
    plan = json.loads((BODIES / "plans" / "api-2k.json").read_text())
    assert len(plan["steady"]) == 40
    for b in plan["prime"] + plan["steady"]:
        assert b["lane"] == "bulk" and b["tier_shapes"] == [[32, 2048]]
    for b in plan["steady"]:
        assert len(b["requests"]) == 6 and 1 <= b["long_bodies"] <= 4
        assert b["wire_bytes"] <= 16384
    assert sorted(i for b in plan["steady"] for i in b["requests"]) == list(range(240))


# -- (b) verdict parity: device == host evaluator, short and long rows together -----


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_pool_verdicts_device_equal_host(engine, seed):
    plan = json.loads((BODIES / "plans" / "api-2k.json").read_text())
    burst = plan["steady"][random.Random(seed).randrange(40)]
    reqs = window(pool(), burst["requests"], seed)
    lengths = sorted(len(r.body) for r in reqs)
    assert lengths[-1] > 1024 and lengths[0] < 1024  # a long row among short ones
    blob = serialize_requests(reqs)
    got = engine.collect(engine.prepare_blob(blob, len(reqs)))
    want = engine.host_fallback.evaluate(reqs)
    for r, a, b in zip(reqs, got, want):
        assert (a.interrupted, a.status, a.rule_id, sorted(a.matched_ids)) == (
            b.interrupted, b.status, b.rule_id, sorted(b.matched_ids)), r.uri
    assert engine.tiering_summary()["windows"] >= 1


# -- (c) tiering ---------------------------------------------------------------------


def _rows_window(rng, n_long: int, n_short: int = 5):
    """``n_long`` JSON bodies of 1,100-2,000 bytes among ``n_short``
    small ones: few long rows among many short."""
    def doc(size):
        fields = {f"k{j}": "w" * rng.randrange(3, 12) for j in range(rng.randrange(3, 9))}
        fields["nonce"] = "%032x" % rng.getrandbits(128)
        fields["text"] = " ".join(
            rng.choice(("order", "blue", "parcel", "monday")) for _ in range(size // 6))[:size]
        return json.dumps(fields).encode()

    reqs = [post("application/json", doc(rng.randrange(1100, 1900))) for _ in range(n_long)]
    reqs += [post("application/json", doc(rng.randrange(20, 300))) for _ in range(n_short)]
    rng.shuffle(reqs)
    return reqs


@pytest.mark.parametrize("cache", ["no-cache", "cold-then-warm"])
@pytest.mark.parametrize("n_long", [0, 1, 3, 6])
def test_tiers_native_equal_python_with_long_rows(engine, n_long, cache):
    rng = random.Random(f"tiers/{n_long}")
    reqs = _rows_window(rng, n_long)
    if cache == "no-cache":
        shapes = _assert_window_parity(engine, reqs, None, f"long{n_long}")
    else:
        _assert_window_parity(engine, reqs, engine.value_cache, f"long{n_long}/cold")
        engine.collect(engine.prepare_blob(serialize_requests(reqs), len(reqs)))
        shapes = _assert_window_parity(engine, reqs, engine.value_cache, f"long{n_long}/warm")
    # Few rows: one tier, as wide as the window's longest row asks for.
    assert len(shapes) == 1
    assert shapes[0][1] == (2048 if n_long else 512)


def _tier_shapes_as_before(row_lengths):
    """The tiering rule as it stood before this PR, on row lengths alone:
    first-fit into the bounds under the window's cap, tiers under 256
    rows merged forward, a trailing one backward."""
    cap = max(32, 1 << (max(row_lengths) - 1).bit_length())
    raw = []
    rest = list(row_lengths)
    for b in [b for b in (64, 256, 1024, 4096, 16384) if b < cap] + [cap]:
        fit = [n for n in rest if n <= b]
        rest = [n for n in rest if n > b]
        if fit:
            raw.append([b, len(fit)])
    merged, i = [], 0
    while i < len(raw):
        b, n = raw[i]
        while n < 256 and i + 1 < len(raw):
            i += 1
            b, n = raw[i][0], n + raw[i][1]
        merged.append([b, n])
        i += 1
    if len(merged) > 1 and merged[-1][1] < 256:
        b, n = merged.pop()
        merged[-1] = [max(merged[-1][0], b), merged[-1][1] + n]
    return [(max(32, 1 << (b - 1).bit_length()), n) for b, n in merged]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_window_without_long_rows_is_tiered_as_before(python_engine, seed):
    rng = random.Random(f"before/{seed}")
    # many short rows, some hundreds of bytes, none over 1,024: enough of
    # them that the rule splits the window (more than 256 a tier)
    reqs = [post("application/x-www-form-urlencoded",
                 "&".join(f"k{j}={'v' * rng.choice((3, 40, 200, 700))}"
                          for j in range(rng.randrange(8, 16))).encode())
            for _ in range(48)]
    tensors = python_engine._tensorize([python_engine.extractor.extract(r) for r in reqs])
    tiers, _nv, _masks = tier_tensors(tensors, None)
    lengths = tensors[1].astype(np.int64)
    if tensors[8].size:
        lengths = np.maximum(lengths, tensors[8].max(axis=0))
    real = lengths[tensors[5] < tensors[6].shape[0]]
    want = _tier_shapes_as_before([int(n) for n in real])
    assert len(want) > 1
    # per tier: its width, and its row pairs as bucketed
    assert [(t[0].shape[1], int(t[5].shape[0])) for t in tiers] == [
        (w, _bucket_rows(n)) for w, n in want]


def test_shapes_of_a_thousand_windows_stay_in_the_lattice(engine):
    """Whatever mix of short and long bodies a window holds, its matcher
    shapes are (rows, width) with rows a bucket of ``_bucket_rows`` and
    width a power of two from 32 up to the window's own cap: the closed
    set a deployment can mint."""
    rng = random.Random("lattice")
    builders = pool()
    seen = set()
    for k in range(1000):
        idxs = rng.sample(range(len(builders)), rng.randrange(1, 9))
        reqs = window(builders, idxs, f"lattice/{k}")
        tiers, _nv, _masks, _cached, _keys, lease = engine._batch_tensors(reqs)
        try:
            for t in tiers:
                rows, width = t[0].shape
                assert rows == _bucket_rows(rows) and width >= 32 and width & (width - 1) == 0
                seen.add((rows, width))
        finally:
            lease.release()
    assert max(w for _r, w in seen) == 2048  # no body of the pool asks for more
    assert len(seen) <= 24, sorted(seen)


# -- counters on the served path ---------------------------------------------------


def _bodied_burst(tag: bytes) -> list[bytes]:
    mp = (b"--xx\r\nContent-Disposition: form-data; name=\"a\"\r\n\r\nv" + tag + b"\r\n--xx--\r\n")
    bodies = [
        (b"application/json", b'{"a": "' + tag + b'", "b": [1, 2, {"c": "d"}]}'),
        (b"application/json", b'{"a": "' + tag + b'", '),  # does not parse
        (b"application/x-www-form-urlencoded", b"a=" + tag + b"&b=c+d%21"),
        (b"multipart/form-data; boundary=xx", mp),
        (b"text/plain", b"hello " + tag),
    ]
    return [b"POST /api/v1/orders HTTP/1.1\r\nHost: x\r\nAccept: */*\r\nUser-Agent: t\r\n"
            b"Content-Type: %s\r\nContent-Length: %d\r\n\r\n%s" % (ctype, len(body), body)
            for ctype, body in bodies]


def _send(port: int, requests: list[bytes]) -> None:
    import socket

    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(b"".join(requests))
        got = b""
        while got.count(b"HTTP/1.1 ") < len(requests):
            chunk = s.recv(65536)
            assert chunk, "connection closed before every reply"
            got += chunk


@pytest.fixture(scope="module")
def sidecar(native_lib):

    from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar

    sc = TpuEngineSidecar(
        SidecarConfig(host="127.0.0.1", port=0, frontend="async", adaptive_enabled=False),
        engine=native_engine(RULES, native_lib))
    sc.start()
    deadline = time.monotonic() + 120
    while sc.serving_mode() != "promoted" and time.monotonic() < deadline:
        time.sleep(0.02)
    assert sc.serving_mode() == "promoted"
    yield sc
    sc.stop()


def test_stats_count_tiers_and_bodies(sidecar):
    before = sidecar.stats()
    _send(sidecar.port, _bodied_burst(b"first"))
    after = sidecar.stats()
    grew = lambda block, key: after[block][key] - before[block][key]
    assert [grew("bodies", k) for k in waf_mod.BODY_COUNTERS[:4]] == [2, 1, 1, 1]
    assert grew("bodies", "parse_errors") == 1
    assert grew("bodies", "bytes_total") == sum(
        len(r.partition(b"\r\n\r\n")[2]) for r in _bodied_burst(b"first"))
    assert grew("tiering", "windows") >= 1 and grew("tiering", "tiers") >= grew("tiering", "windows")
    assert 0 < grew("tiering", "real_bytes") < grew("tiering", "cells")
    metrics = sidecar.render_metrics()
    for name in ("cko_tiering_windows_total", "cko_tiering_cells_total",
                 "cko_tiering_real_bytes_total", "cko_bodies_json_total",
                 "cko_bodies_multipart_total", "cko_bodies_parse_errors"):
        assert f"\n{name} " in metrics, name


@pytest.mark.parametrize("repeats", [1, 3])
def test_batcher_requests_counts_cache_hits_and_duplicates(sidecar, repeats):
    """``batcher.requests`` is every request the batcher answered: a
    repeat the verdict cache or the in-window dedup answered is a
    request, though no batch."""
    burst = _bodied_burst(b"again%d" % repeats)
    _send(sidecar.port, burst)  # first sight: rides the device, enters the cache
    before = sidecar.stats()
    _send(sidecar.port, burst * repeats)
    sent = len(burst) * repeats
    # repeats are counted once per window, right after its collect has
    # resolved the window's future: the reply can be here a moment sooner
    deadline = time.monotonic() + 2
    while True:
        after = sidecar.stats()
        if (after["batcher"]["requests"] - before["batcher"]["requests"] >= sent
                or time.monotonic() > deadline):
            break
        time.sleep(0.005)
    assert after["batcher"]["requests"] - before["batcher"]["requests"] == sent
    repeated = (after["verdict_cache"]["hits_total"] - before["verdict_cache"]["hits_total"]
                + after["verdict_cache"]["window_dedup_rows"]
                - before["verdict_cache"]["window_dedup_rows"])
    assert repeated == sent
    assert after["bodies"]["json_total"] == before["bodies"]["json_total"]  # none was read again
