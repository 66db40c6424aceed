"""Differential tests: the C++ host runtime vs the Python reference path.

The native library must produce bit-for-bit identical tensors to
``engine/request.py`` + ``engine/waf.py:_tensorize`` on the same requests —
randomized corpora over every transform family, arg shapes, JSON bodies,
cookies, and selector-regex kinds. The library is ``conftest.py``'s
``native_lib``, loaded for this module alone (``native_loaded``).
"""

import random
import string

import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
from coraza_kubernetes_operator_tpu.compiler.transforms_host import (
    TRANSFORMS,
    apply_pipeline,
)
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
pytestmark = pytest.mark.usefixtures("native_loaded")

RULES = r"""
SecRuleEngine On
SecRequestBodyAccess On
SecRule ARGS|REQUEST_URI "@rx (?i:\bunion\b.{0,40}\bselect\b)" \
  "id:1,phase:2,deny,status:403,t:none,t:urlDecodeUni,t:lowercase"
SecRule ARGS_NAMES|ARGS "@contains evil" "id:2,phase:2,deny,status:403,t:none,t:htmlEntityDecode"
SecRule REQUEST_HEADERS:User-Agent "@pm sqlmap nikto" "id:3,phase:1,deny,status:403,t:lowercase"
SecRule REQUEST_HEADERS:'/^X-Custom-.*/' "@contains inject" "id:4,phase:1,deny,status:403"
SecRule REQUEST_COOKIES "@rx session=admin" "id:5,phase:1,deny,status:403,t:normalizePath"
SecRule REQUEST_BODY "@contains attack" "id:6,phase:2,deny,status:403,t:base64Decode"
SecRule ARGS "@rx select" "id:7,phase:2,pass,t:cmdLine,setvar:'tx.score=+2'"
SecRule TX:score "@ge 4" "id:8,phase:2,deny,status:403"
SecRule &ARGS "@gt 8" "id:9,phase:2,deny,status:403"
SecRule REQUEST_URI "@contains ../" "id:10,phase:1,deny,status:403,t:none,t:removeComments,t:jsDecode,t:cssDecode"
SecRule QUERY_STRING "@contains x" "id:11,phase:1,pass,t:compressWhitespace,t:trim,t:removeWhitespace"
SecRule REQUEST_LINE "@contains probe" "id:12,phase:1,deny,status:403,t:hexDecode"
"""


def _random_requests(n: int, seed: int) -> list[HttpRequest]:
    rng = random.Random(seed)
    alphabet = string.printable + "\x00\xe9\xff%&=+;"
    reqs = []
    for _i in range(n):
        kind = rng.randrange(6)
        headers = [("Host", "test.local"), ("User-Agent", rng.choice(
            ["Mozilla/5.0", "sqlmap/1.7", "curl/8", "NIKTO scan"]))]
        body = b""
        uri = "/"
        method = rng.choice(["GET", "POST", "PUT"])
        if kind == 0:
            q = "&".join(
                f"{''.join(rng.choices(alphabet, k=rng.randrange(1, 8)))}="
                f"{''.join(rng.choices(alphabet, k=rng.randrange(0, 40)))}"
                for _ in range(rng.randrange(0, 6))
            )
            uri = f"/p?{q}"
        elif kind == 1:
            uri = "/?q=union+%73elect+a+from+b&r=%u0041%3Cscript"
            headers.append(("X-Custom-Probe", "try to inject here"))
        elif kind == 2:
            body = "&".join(
                f"k{j}={''.join(rng.choices(alphabet, k=rng.randrange(0, 60)))}"
                for j in range(rng.randrange(1, 5))
            ).encode("latin-1", "replace")
            headers.append(("Content-Type", "application/x-www-form-urlencoded"))
        elif kind == 3:
            body = (
                b'{"user": {"name": "bob\\u00e9", "ids": [1, 2.5, true, null],'
                b' "note": "eviltext /* c */"}, "n": 1e30, "b": -0.125}'
            )
            headers.append(("Content-Type", "application/json"))
        elif kind == 4:
            body = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 80)))
            headers.append(("Content-Type", "application/json"))  # invalid json
        else:
            headers.append(("Cookie", " session=admin; a = b;theme=dark "))
            uri = "/a/../b/./c%2e%2e/"
        reqs.append(
            HttpRequest(
                method=method, uri=uri, version="HTTP/1.1",
                headers=headers, body=body, remote_addr="10.1.2.3",
            )
        )
    return reqs


@pytest.fixture(scope="module")
def engine(native_loaded):
    return WafEngine(RULES)


def test_native_available(engine):
    assert engine.native_enabled


def test_differential_tensorize(engine):
    for seed in (1, 2, 3):
        requests = _random_requests(64, seed)
        extractions = [engine.extractor.extract(r) for r in requests]
        py = engine._tensorize(extractions)
        nat = engine._native.tensorize(requests)
        names = [
            "data", "lengths", "kind1", "kind2", "kind3", "req_id",
            "numvals", "vdata", "vlengths",
        ]
        for name, a, b in zip(names, py, nat):
            a = np.asarray(a)
            b = np.asarray(b)
            assert a.shape == b.shape, (seed, name, a.shape, b.shape)
            assert (a == b).all(), (
                seed, name, np.argwhere(a != b)[:5],
            )


def test_differential_verdicts(engine):
    requests = _random_requests(128, 7)
    native_verdicts = engine.evaluate(requests)  # native path
    # force python path
    avail, engine._native._ctx = engine._native._ctx, None
    try:
        py_verdicts = engine.evaluate(requests)
    finally:
        engine._native._ctx = avail
    for i, (a, b) in enumerate(zip(native_verdicts, py_verdicts)):
        assert (a.interrupted, a.status, a.rule_id, a.matched_ids) == (
            b.interrupted, b.status, b.rule_id, b.matched_ids
        ), (i, requests[i].uri)


def test_transform_parity_exhaustive():
    """Every native transform opcode agrees with its Python reference on
    adversarial byte strings."""
    from coraza_kubernetes_operator_tpu.native import _OPCODES

    rng = random.Random(42)
    cases = [
        b"", b"a", b"%41%zz%", b"%u0041%u00e9%U1F600x", b"+a+b%2",
        b"&#65;&#x41;&amp;&unknown;&#xZZ;&#1114112;", b"a\x00b\x00",
        b"  a  b\t\nc  ", b"/a/../../b/./c/", b"a\\x41\\u0042\\101\\8\\",
        b"\\41 x\\000041y\\g", b"SGVsbG8gV29ybGQ=!after", b"@@SGVsbG8=",
        b"48656c6c6fzz21", b"/* c */ x -- y\n z # w\n<!-- h --> t",
        b"a,b;c\\d\"e'f^g / (h", b"\xc3\xa9\xe2\x82\xac\xf0\x9f\x98\x80\xff\xfe",
        b"caf\xe9 \x80\xc2", bytes(range(256)),
    ]
    for _ in range(200):
        cases.append(bytes(rng.randrange(256) for _ in range(rng.randrange(0, 50))))

    # build one engine per... cheaper: use a tiny ctx-free check through a
    # synthetic ruleset exercising each transform as its own host pipeline is
    # heavy; instead compare via ctypes on a throwaway context is not exposed.
    # The pipeline-level differential below covers compositions; here we
    # check single ops through a minimal one-rule engine per transform.
    name_by_op = {}
    for name, op in _OPCODES.items():
        name_by_op.setdefault(op, name)
    for name in name_by_op.values():
        if name in ("none",):
            continue
        rules = (
            "SecRuleEngine On\nSecRequestBodyAccess On\n"
            f'SecRule ARGS "@contains zzneverzz" "id:1,phase:2,deny,status:403,t:{name}"\n'
        )
        try:
            eng = WafEngine(rules)
        except Exception:
            continue  # transform not accepted in seclang position
        if not eng.native_enabled:
            continue
        host = eng.compiled.host_pipelines()
        if not host:
            continue  # compiled to a device pipeline; covered elsewhere
        names = list(host[0][1])
        for case in cases:
            req = HttpRequest(
                uri="/?k=" + "".join("%%%02x" % b for b in case)
            )
            nat = eng._native.tensorize([req])
            extr = [eng.extractor.extract(req)]
            py = eng._tensorize(extr)
            assert (np.asarray(py[7]) == np.asarray(nat[7])).all(), (
                name, case, apply_pipeline(case, names),
            )


def test_native_sqli_differential():
    """The C++ SQLi machine (cko_sqli) must agree with compiler/sqli.py
    byte-for-byte: same tokenizer semantics, blob-shipped tables."""
    from coraza_kubernetes_operator_tpu.compiler.sqli import (
        _ATTACK_CORPUS,
        is_sqli,
    )
    from coraza_kubernetes_operator_tpu.native import (
        load_library,
        serialize_config,
    )

    rules = (
        "SecRuleEngine On\n"
        'SecRule ARGS "@detectSQLi" "id:1,phase:2,deny,status:403,t:none,t:urlDecodeUni"\n'
    )
    crs = compile_rules(rules)
    lib = load_library()
    assert lib is not None
    blob = serialize_config(crs)
    assert blob is not None, "hostop ruleset must serialize natively now"
    ctx = lib.cko_ctx_new(blob, len(blob))
    assert ctx

    benign = [
        "hello world", "the quick brown fox", "1 plus 1", "a=1&b=2",
        "O'Brien", "12:30pm", "path/to/file.txt", "x" * 50, "",
        "select a seat", "drop me a line", "union station",
        "I'd like 2 to 1 odds", "price > 100 and color = blue?",
    ]
    rng = random.Random(3)
    fuzz = []
    alpha = string.printable
    for _ in range(400):
        fuzz.append("".join(rng.choice(alpha) for _ in range(rng.randrange(0, 40))))
    try:
        for s in _ATTACK_CORPUS + benign + fuzz:
            b = s.encode("latin-1", "replace")
            want = is_sqli(b)[0]
            got = lib.cko_sqli(ctx, b, len(b)) == 1
            assert got == want, (s, want, got)
    finally:
        lib.cko_ctx_free(ctx)


def test_native_sqli_ruleset_verdict_parity():
    """End-to-end: a @detectSQLi ruleset runs on the native tensorizer and
    produces identical verdicts to the python extraction path."""
    rules = (
        "SecRuleEngine On\n"
        'SecDefaultAction "phase:2,log,deny,status:403"\n'
        'SecRule ARGS "@detectSQLi" "id:900,phase:2,deny,status:403,t:none,t:urlDecodeUni"\n'
    )
    eng = WafEngine(rules)
    assert eng.native_enabled, "detectSQLi ruleset must ride the native path"
    reqs = [
        HttpRequest(uri="/?q=hello"),
        HttpRequest(uri="/?q=1%27%20or%20%271%27%3D%271"),
        HttpRequest(uri="/?q=union+select+password+from+users"),
        HttpRequest(uri="/?name=O%27Brien"),
    ]
    native_verdicts = eng.evaluate(reqs)
    import coraza_kubernetes_operator_tpu.engine.waf as waf_mod

    saved = eng._native
    class _Off:
        available = False
    eng._native = _Off()
    try:
        python_verdicts = eng.evaluate(reqs)
    finally:
        eng._native = saved
    assert [v.interrupted for v in native_verdicts] == [
        v.interrupted for v in python_verdicts
    ] == [False, True, True, False]


def test_native_xss_differential():
    """C++ html5 XSS machine vs compiler/xss.py, byte-for-byte."""
    from coraza_kubernetes_operator_tpu.compiler.xss import is_xss
    from coraza_kubernetes_operator_tpu.native import load_library, serialize_config

    crs = compile_rules(
        'SecRule ARGS "@detectXSS" "id:1,phase:2,deny,status:403,t:none"'
    )
    lib = load_library()
    blob = serialize_config(crs)
    assert blob is not None, "xss hostop ruleset must serialize natively"
    ctx = lib.cko_ctx_new(blob, len(blob))
    assert ctx

    corpus = [
        '<script>alert(1)</script>', '<img src=x onerror=alert(1)>',
        '" onmouseover="alert(1)', "' onfocus='alert(1)", '` onclick=a',
        'javascript:alert(1)', 'JaVa\tScRiPt:x', '<svg/onload=a>',
        '<iframe src=//e>', '<style>x</style>', 'data:text/html,x',
        '<!ENTITY x>', '<!--[if IE]>', '<math href=javascript:x>',
        'hello', 'a < b and b > c', '<p>text</p>', "O'Brien",
        '<a href="https://ok/">l</a>', 'x = 1', 'mailto:a@b',
        '<div class="x">y</div>', 'price <100', '12:30',
    ]
    rng = random.Random(11)
    for _ in range(400):
        corpus.append(
            "".join(rng.choice(string.printable) for _ in range(rng.randrange(0, 40)))
        )
    try:
        for s in corpus:
            b = s.encode("latin-1", "replace")
            want = is_xss(b)
            got = lib.cko_xss(ctx, b, len(b)) == 1
            assert got == want, (s, want, got)
    finally:
        lib.cko_ctx_free(ctx)


def test_native_multipart_parity():
    """Multipart extraction parity: python vs C++ on framing edge cases
    (incl. a decoy header containing 'content-disposition')."""
    rules = (
        "SecRuleEngine On\nSecRequestBodyAccess On\n"
        'SecRule MULTIPART_STRICT_ERROR "@eq 1" "id:1,phase:2,deny,status:403"\n'
        'SecRule ARGS "@contains evilvalue" "id:2,phase:2,deny,status:403"\n'
        'SecRule FILES "@rx (?i)\\.php$" "id:3,phase:2,deny,status:403"\n'
    )
    eng = WafEngine(rules)
    assert eng.native_enabled
    hdr = [("Content-Type", "multipart/form-data; boundary=bXb")]
    bodies = [
        # clean
        b"--bXb\r\nContent-Disposition: form-data; name=\"a\"\r\n\r\nok\r\n--bXb--\r\n",
        # decoy header containing the substring, real disposition after
        b"--bXb\r\nX-Content-Disposition-Hint: zz\r\nContent-Disposition: form-data; name=\"a\"\r\n\r\nevilvalue\r\n--bXb--\r\n",
        # missing disposition entirely
        b"--bXb\r\nX-Other: 1\r\n\r\nv\r\n--bXb--\r\n",
        # file part
        b"--bXb\r\nContent-Disposition: form-data; name=\"f\"; filename=\"x.PHP\"\r\n\r\nz\r\n--bXb--\r\n",
        # unterminated
        b"--bXb\r\nContent-Disposition: form-data; name=\"a\"\r\n\r\nv\r\n",
    ]
    reqs = [
        HttpRequest(uri="/u", method="POST", headers=hdr, body=b) for b in bodies
    ]
    native = [(v.interrupted, v.rule_id) for v in eng.evaluate(reqs)]

    saved = eng._native

    class _Off:
        available = False

    eng._native = _Off()
    try:
        python = [(v.interrupted, v.rule_id) for v in eng.evaluate(reqs)]
    finally:
        eng._native = saved
    assert native == python, (native, python)
    assert native[0] == (False, None)
    assert native[1] == (True, 2)  # decoy must not mask the real part
