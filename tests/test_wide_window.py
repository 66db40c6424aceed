"""A window of hundreds of unique rows (ISSUE 45; wafbench's
``crs-ingress.wide-u512-c1``).

No cell before it served more than 32 rows a launch. Here, small and on
the CPU: the conv tier's budget as a function of the device's memory
(``models/waf_model.py:seg_chunk_budget``, PR 46: an eighth of a v5e's
memory in bf16, 2^27 where the device reports none, as this CPU) and the
plans it gives crs-lite's and the 5,000-rule feed's conv tiers at every
shape a benchmark cell serves (``512x512``: one conv on a v5e, row chunks
of 5 x 104 without memory stats), with shapes past the v5e's budget that
still go ``rows`` / ``tiles``; an
engine on the bundled CRS-shaped rule set given one window of 400 unique
rows on a row-chunked plan, held request for request to the host
evaluator; what a tier whose every row the value cache answered launches
(a hundred requests keep their short pair rows on a tier of their own,
``engine/waf.py:_MIN_TIER_ROWS``: steady, its matcher runs one padding row,
``1x64``); and what ``tiering.rows`` / ``rows_padded`` count, on the Python
tensorizer and the native plan alike.
"""

from __future__ import annotations

import random
from pathlib import Path

import jax
import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
from coraza_kubernetes_operator_tpu.models import waf_model
from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols
from conftest import native_engine
from wafbench.harness import read_rules

REPO = Path(__file__).resolve().parents[1]
# The bundled CRS-shaped rule set, and two rules over what CRS reads of
# every request: header and cookie names and values (the short pair rows),
# the query string and the request line.
MINI = (REPO / "ftw/rules/base.conf").read_text() + (REPO / "ftw/rules/crs-mini.conf").read_text() + r"""
SecRule REQUEST_HEADERS|REQUEST_HEADERS_NAMES|REQUEST_COOKIES|REQUEST_COOKIES_NAMES \
  "@contains evilmonkey" "id:990001,phase:1,deny,status:403,t:none,t:lowercase"
SecRule REQUEST_HEADERS|REQUEST_COOKIES "@rx (?i:\$\{jndi:)" "id:990002,phase:2,deny,status:403,t:none"
SecRule QUERY_STRING|REQUEST_LINE "@contains evilmonkey" "id:990003,phase:2,deny,status:403,t:none"
"""
SEED = 45
_ATTACKS = (
    "1%27%20UNION%20SELECT%20password%20FROM%20users--", "1%20or%201=1",
    "<script>alert(1)</script>", "../../../../etc/passwd", "sleep(10)%20benchmark(1)",
)
_AGENTS = ("Mozilla/5.0 (X11; Linux x86_64) Firefox/124.0", "curl/8.5.0",
           "Mozilla/5.0 (Windows NT 10.0; Win64; x64) Chrome/123.0.6312")
_SCANNERS = ("sqlmap/1.7", "nikto/2.5")


def _requests(n: int, seed: int, salt_len: int = 300) -> list[HttpRequest]:
    """``n`` header-only GETs, a fifth of them attacks, each with one
    argument of ``salt_len`` hex that no other request has: four unique
    rows a request on the widest tier (the value, the URI, the query
    string, the request line), the rest (names, hosts, agents) shared."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        salt = "".join(rng.choice("0123456789abcdef") for _ in range(salt_len))
        path = rng.choice(("/", "/api/v1/items", "/login", "/search"))
        q = f"q={rng.choice(_ATTACKS)}&" if rng.random() < 0.2 else ""
        out.append(HttpRequest(
            uri=f"{path}?{q}ckosmoke={salt}",
            headers=[("Host", rng.choice(("a.example", "b.example"))),
                     ("User-Agent", rng.choice(_SCANNERS if rng.random() < 0.05 else _AGENTS)),
                     ("Accept", "*/*"),
                     ("Cookie", f"session={i:04x}{rng.randrange(1 << 24):06x}")]))
    return out


def _told(verdicts) -> list[tuple]:
    return [(v.status if v.interrupted else 200, v.rule_id if v.interrupted else None)
            for v in verdicts]


def _matchers() -> list[dict]:
    return [e for e in EXEC_CACHE.stats()["executables"] if e["name"].startswith("cko_match_")]


@pytest.fixture(scope="module")
def crs_lite():
    with pytest.MonkeyPatch.context() as mp:
        for k in ("CKO_AUTOMATA",):
            mp.delenv(k, raising=False)
        return WafEngine(read_rules(REPO / "wafbench/configs/crs-lite-pl2/rules"))


# ``memory_stats()["bytes_limit"]`` of one v5e chip (my chip run, PR 46).
V5E = 16_909_336_064


@pytest.mark.parametrize("bytes_limit,elements", [
    (None, 2**27),          # XLA:CPU reports no memory: tier-1, every plan as before PR 46
    (0, 2**27),
    (16 * 2**30, 2**30),    # 16 GiB: an eighth of it, in bf16
    (V5E, 1_056_833_504),   # what a v5e says it has
])
def test_the_conv_tiers_budget_follows_the_devices_memory(monkeypatch, bytes_limit, elements):
    assert waf_model.seg_chunk_budget(bytes_limit) == elements
    monkeypatch.setattr(waf_model, "_device_bytes_limit", lambda: bytes_limit)
    assert waf_model._seg_chunk_elems() == elements
    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 12345)  # the tests' override wins
    assert waf_model._seg_chunk_elems() == 12345


def test_this_cpu_reports_no_memory_and_nothing_reads_the_environment():
    assert waf_model._device_bytes_limit.__wrapped__() is None
    assert waf_model._SEG_CHUNK_ELEMS is None and waf_model._seg_chunk_elems() == 2**27
    package = Path(waf_model.__file__).parents[1]
    assert not [f for f in package.rglob("*.py") if "CKO_SEG_CHUNK_ELEMENTS" in f.read_text()]


@pytest.fixture(scope="module")
def conv_specs(crs_lite):
    """The conv tier's specs of the two rule texts the CRS cells serve:
    crs-lite (2,496 columns) and crs-lite behind the 5,000-rule feed
    (12,498), built as ``build_model`` does, no engine for the feed."""
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules

    feed = read_rules(REPO / "wafbench/configs/crs-lite-pl2-custom5k/rules")
    return {2496: [sb.spec for sb in crs_lite.model.segs],
            12498: [sb.spec for sb in waf_model.build_model(compile_rules(feed)).segs]}


@pytest.mark.parametrize("bytes_limit,columns,rows,width,path,chunks,per_chunk,tiles", [
    # without memory stats (2^27): the plans every cell rode until PR 46
    (None, 2496, 512, 512, "rows", 5, 104, 8),     # crs-ingress.wide-u512-c1
    (None, 2496, 32, 512, "direct", 1, 32, 8),     # the three crs-lite cells
    (None, 2496, 32, 2048, "rows", 2, 16, 8),      # crs-bodies.api-2k-c1
    (None, 12498, 32, 512, "rows", 2, 16, 8),      # crs-custom5k.ftw-salted-c1
    (None, 12498, 32, 2048, "tiles", 1, 32, 12),   # crs-custom5k-bodies.api-2k-c1
    # on a v5e: every shape a cell serves is one conv and one pass of the chains
    (V5E, 2496, 512, 512, "direct", 1, 512, 8),
    (V5E, 2496, 32, 512, "direct", 1, 32, 8),
    (V5E, 2496, 32, 2048, "direct", 1, 32, 8),
    (V5E, 12498, 32, 512, "direct", 1, 32, 8),
    (V5E, 12498, 32, 2048, "direct", 1, 32, 8),
    # ... and past its budget the rows are chunked and the columns tiled as before
    (V5E, 2496, 256, 8192, "rows", 6, 48, 8),
    (V5E, 12498, 512, 512, "rows", 4, 128, 8),
    (V5E, 12498, 64, 32768, "tiles", 1, 64, 29),
])
def test_the_plan_at_each_cell_s_shape_follows_the_budget(monkeypatch, conv_specs, bytes_limit,
                                                          columns, rows, width, path, chunks,
                                                          per_chunk, tiles):
    monkeypatch.setattr(waf_model, "_device_bytes_limit", lambda: bytes_limit)
    specs = conv_specs[columns]
    plan = waf_model.plan_segment_tier(specs, tuple(range(len(specs))), rows, width, long_ok=True)
    assert (plan.path, plan.row_chunks, plan.rows_per_chunk) == (path, chunks, per_chunk)
    said = plan.summary()
    assert said["columns"] == sum(conv_n2_cols(s) for s in specs) == columns
    assert said["column_tiles"] == tiles
    # which rule engaged: the budget the plan was cut to, and what it holds
    budget = said["budget_elements"]
    assert budget == waf_model.seg_chunk_budget(bytes_limit)
    positions = width + 2
    assert per_chunk * positions * said["columns_per_tile_max"] <= budget
    if path == "direct":
        assert rows * positions * columns <= budget
    else:  # the fewest chunks the budget allows: eight rows more would not fit
        assert rows * positions * columns > budget
    if path == "rows":
        rows_fit = budget // (positions * columns) // 8 * 8
        assert per_chunk <= rows_fit and chunks == -(-rows // rows_fit)


def test_the_engine_says_the_plan_and_its_budget_of_the_wide_window(crs_lite, monkeypatch):
    """``tier_seg_plan`` is what ``compile_cache.executables[].seg_plan``
    shows of a matcher: on a v5e the ingress cell's ``512x512`` is direct."""
    assert waf_model.tier_seg_plan(crs_lite.model, 512, 512).summary()["path"] == "rows"
    monkeypatch.setattr(waf_model, "_device_bytes_limit", lambda: V5E)
    said = waf_model.tier_seg_plan(crs_lite.model, 512, 512).summary()
    assert (said["path"], said["row_chunks"], said["rows_per_chunk"]) == ("direct", 1, 512)
    assert said["budget_elements"] == V5E // 16 and said["reach_gaps"] == 0


@pytest.fixture(scope="module")
def chunked():
    """The bundled rule set's engine with the conv budget cut so that a
    window of 512 rows of 512 bytes goes through in four chunks of 128
    rows, as crs-lite's goes through in five under the 2^27 of a device
    that reports no memory (and went on a v5e until PR 46)."""
    engine = native_engine(MINI, None)
    n2 = sum(conv_n2_cols(sb.spec) for sb in engine.model.segs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(waf_model, "_SEG_CHUNK_ELEMS", 128 * 514 * n2)
        EXEC_CACHE.clear()
        jax.clear_caches()
        yield engine
    EXEC_CACHE.clear()
    jax.clear_caches()


def test_a_window_of_400_rows_on_row_chunks_gets_the_host_verdicts(chunked):
    reqs = _requests(100, SEED)
    want = _told(chunked.host_fallback.evaluate(reqs))
    assert 10 <= sum(s != 200 for s, _ in want) <= 40 and len({r for _, r in want}) >= 4
    before = chunked.tiering_summary()
    got = _told(chunked.evaluate(reqs))
    grew = {k: v - before[k] for k, v in chunked.tiering_summary().items()}
    assert got == want
    wide = [e for e in _matchers() if e["name"] == "cko_match_512x512"]
    assert wide and wide[0]["seg_plan"]["path"] == "rows"
    assert (wide[0]["seg_plan"]["row_chunks"], wide[0]["seg_plan"]["rows_per_chunk"]) == (4, 128)
    assert grew["windows"] == 1 and grew["long_scan_launches"] == 0
    # four salted rows a request on the wide tier, the short rows cold beside them
    assert grew["tiers"] == 2
    assert 400 + 128 < grew["rows"] <= grew["rows_padded"] == 512 + 256


def test_a_tier_the_value_cache_answered_whole_launches_one_padding_row(chunked):
    """The second window of a hundred requests: its short rows (names,
    hosts, agents, cookies seen before) have a tier of their own, 1,000
    pair rows and none to match. Its matcher runs on one padding row
    (``1x64``, one more shape), it counts no row, and the verdicts are the
    host evaluator's."""
    first = _requests(100, SEED + 1)
    chunked.evaluate(first)
    # the same visitors (cookies, hosts, agents), new salted arguments
    again = [HttpRequest(uri=b.uri, headers=a.headers)
             for a, b in zip(first, _requests(100, SEED + 2))]
    want = _told(chunked.host_fallback.evaluate(again))
    before = chunked.tiering_summary()
    got = _told(chunked.evaluate(again))
    grew = {k: v - before[k] for k, v in chunked.tiering_summary().items()}
    assert got == want and any(s != 200 for s, _ in want)
    assert grew["windows"] == 1 and grew["tiers"] == 2
    assert grew["rows"] == 400 and grew["rows_padded"] == 1 + 512
    assert grew["cells"] == 64 + 512 * 512
    # a match slab a tier and the post slab
    assert grew["host_operands"] == 3
    assert {"cko_match_1x64", "cko_match_512x512"} <= {e["name"] for e in _matchers()}


@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_tiering_rows_count_what_a_window_launched(native, native_lib):
    """32 requests of four salted rows each and a dozen shared short rows:
    one tier (its pair rows are under ``_MIN_TIER_ROWS``), counted before
    padding and as bucketed; with the value cache (cold: every unique row
    is a miss) and without it the same."""
    reqs = _requests(32, SEED + 3, salt_len=40)
    unique = None
    for cache_on in (True, False):
        engine = native_engine(MINI, native_lib if native else None)
        assert bool(getattr(engine._native, "tiered", False)) == native
        if cache_on:
            tiers, _nv, _masks, _cached, keys, lease = engine._batch_tensors(reqs)
            if lease is not None:
                lease.release()
            assert len(tiers) == 1 and tiers[0][0].shape[0] == 256
            unique = len(keys[0])
        else:
            engine.value_cache = None
        before = engine.tiering_summary()
        engine.evaluate(reqs)
        grew = {k: v - before[k] for k, v in engine.tiering_summary().items()}
        assert grew["windows"] == grew["tiers"] == 1
        assert grew["rows"] == unique and 128 < unique <= 256 == grew["rows_padded"]
