// Native host runtime: request extraction + batch tensorization.
//
// The Python data-plane facade (engine/waf.py WafEngine) spends ~80% of its
// end-to-end budget in per-request Python object churn: query/body parsing,
// URL decoding, target/kind resolution and padded-array fill. This library
// is the C++ tier of that path (the role the reference delegates to native
// Envoy/WASM code outside its repo — SURVEY §2.2): semantics mirror
// engine/request.py (extraction), compiler/transforms_host.py (host byte
// transforms) and engine/waf.py:_tensorize (row packing) exactly, and the
// differential tests in tests/test_native.py hold the two implementations
// bit-for-bit equal on randomized requests.
//
// C ABI (ctypes): cko_ctx_new(config blob) -> handle; cko_tensorize(handle,
// request blob) -> result handle; cko_result_* getters fill caller-allocated
// numpy buffers. All integers little-endian; layouts documented next to the
// Python serializer (coraza_kubernetes_operator_tpu/native/__init__.py).

#include <algorithm>
#include <cctype>
#include <charconv>
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

using bytes = std::string;  // byte strings (may contain NUL)

// ---------------------------------------------------------------------------
// small helpers
// ---------------------------------------------------------------------------

inline bool is_hex(uint8_t c) {
  return (c >= '0' && c <= '9') || (c >= 'a' && c <= 'f') ||
         (c >= 'A' && c <= 'F');
}
inline int hex_val(uint8_t c) {
  if (c <= '9') return c - '0';
  if (c >= 'a') return c - 'a' + 10;
  return c - 'A' + 10;
}
inline bool is_ws(uint8_t c) {
  return c == ' ' || c == '\t' || c == '\n' || c == '\r' || c == '\f' ||
         c == '\v';
}
inline bytes lower(const bytes& s) {
  bytes out = s;
  for (auto& c : out)
    if (c >= 'A' && c <= 'Z') c += 32;
  return out;
}

// ---------------------------------------------------------------------------
// transforms (semantics: compiler/transforms_host.py)
// ---------------------------------------------------------------------------

enum TransformOp : uint8_t {
  OP_NONE = 0, OP_LOWERCASE, OP_UPPERCASE, OP_URLDECODE, OP_URLDECODEUNI,
  OP_URLENCODE, OP_HTMLENTITYDECODE, OP_REMOVENULLS, OP_REPLACENULLS,
  OP_REMOVEWHITESPACE, OP_COMPRESSWHITESPACE, OP_TRIM, OP_TRIMLEFT,
  OP_TRIMRIGHT, OP_REMOVECOMMENTS, OP_REMOVECOMMENTSCHAR, OP_REPLACECOMMENTS,
  OP_NORMALIZEPATH, OP_NORMALIZEPATHWIN, OP_CMDLINE, OP_JSDECODE,
  OP_CSSDECODE, OP_BASE64DECODE, OP_BASE64DECODEEXT, OP_BASE64ENCODE,
  OP_HEXDECODE, OP_HEXENCODE, OP_ESCAPESEQDECODE, OP_UTF8TOUNICODE,
  OP_LENGTH,
  OP_COUNT_
};

bytes t_urldecode(const bytes& d) {
  bytes out;
  out.reserve(d.size());
  size_t i = 0, n = d.size();
  while (i < n) {
    uint8_t c = d[i];
    if (c == '%' && i + 2 < n && is_hex(d[i + 1]) && is_hex(d[i + 2])) {
      out.push_back((char)(hex_val(d[i + 1]) * 16 + hex_val(d[i + 2])));
      i += 3;
    } else if (c == '+') {
      out.push_back(' ');
      i += 1;
    } else {
      out.push_back((char)c);
      i += 1;
    }
  }
  return out;
}

bytes t_urldecodeuni(const bytes& d) {
  bytes out;
  out.reserve(d.size());
  size_t i = 0, n = d.size();
  while (i < n) {
    uint8_t c = d[i];
    if (c == '%') {
      if (i + 5 < n && (d[i + 1] == 'u' || d[i + 1] == 'U') &&
          is_hex(d[i + 2]) && is_hex(d[i + 3]) && is_hex(d[i + 4]) &&
          is_hex(d[i + 5])) {
        int val = (hex_val(d[i + 2]) << 12) | (hex_val(d[i + 3]) << 8) |
                  (hex_val(d[i + 4]) << 4) | hex_val(d[i + 5]);
        out.push_back((char)(val & 0xFF));
        i += 6;
        continue;
      }
      if (i + 2 < n && is_hex(d[i + 1]) && is_hex(d[i + 2])) {
        out.push_back((char)(hex_val(d[i + 1]) * 16 + hex_val(d[i + 2])));
        i += 3;
        continue;
      }
      out.push_back('%');
      i += 1;
    } else if (c == '+') {
      out.push_back(' ');
      i += 1;
    } else {
      out.push_back((char)c);
      i += 1;
    }
  }
  return out;
}

bytes t_htmlentitydecode(const bytes& d) {
  bytes out;
  out.reserve(d.size());
  size_t i = 0, n = d.size();
  while (i < n) {
    uint8_t c = d[i];
    if (c != '&') {
      out.push_back((char)c);
      i += 1;
      continue;
    }
    size_t j = i + 1;
    if (j < n && d[j] == '#') {
      j += 1;
      if (j < n && (d[j] == 'x' || d[j] == 'X')) {
        j += 1;
        size_t start = j;
        while (j < n && is_hex(d[j]) && j - start < 7) j++;
        if (j > start && j < n && d[j] == ';') {
          unsigned long val = strtoul(d.substr(start, j - start).c_str(), nullptr, 16);
          out.push_back((char)(val & 0xFF));
          i = j + 1;
          continue;
        }
      } else {
        size_t start = j;
        while (j < n && d[j] >= '0' && d[j] <= '9' && j - start < 7) j++;
        if (j > start && j < n && d[j] == ';') {
          unsigned long val = strtoul(d.substr(start, j - start).c_str(), nullptr, 10);
          out.push_back((char)(val & 0xFF));
          i = j + 1;
          continue;
        }
      }
    } else {
      size_t start = j;
      while (j < n && (isalnum((uint8_t)d[j])) && j - start < 8 &&
             (uint8_t)d[j] < 0x80)
        j++;
      if (j < n && d[j] == ';') {
        bytes name = lower(d.substr(start, j - start));
        int v = -1;
        if (name == "quot") v = 0x22;
        else if (name == "amp") v = 0x26;
        else if (name == "lt") v = 0x3C;
        else if (name == "gt") v = 0x3E;
        else if (name == "nbsp") v = 0xA0;
        if (v >= 0) {
          out.push_back((char)v);
          i = j + 1;
          continue;
        }
      }
    }
    out.push_back('&');
    i += 1;
  }
  return out;
}

bytes t_compresswhitespace(const bytes& d) {
  bytes out;
  out.reserve(d.size());
  bool in_ws = false;
  for (uint8_t c : d) {
    if (is_ws(c)) {
      if (!in_ws) out.push_back(' ');
      in_ws = true;
    } else {
      out.push_back((char)c);
      in_ws = false;
    }
  }
  return out;
}

bytes t_replacecomments(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  while (i < n) {
    if (d[i] == '/' && i + 1 < n && d[i + 1] == '*') {
      size_t end = d.find("*/", i + 2);
      out.push_back(' ');
      if (end == bytes::npos) break;
      i = end + 2;
    } else {
      out.push_back(d[i]);
      i += 1;
    }
  }
  return out;
}

bytes t_removecomments(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  while (i < n) {
    if (d[i] == '/' && i + 1 < n && d[i + 1] == '*') {
      size_t end = d.find("*/", i + 2);
      if (end == bytes::npos) break;
      i = end + 2;
      continue;
    }
    if (d.compare(i, 4, "<!--") == 0) { i += 4; continue; }
    if (d.compare(i, 3, "-->") == 0) { i += 3; continue; }
    if (d.compare(i, 2, "--") == 0 || d[i] == '#') {
      size_t nl = d.find('\n', i);
      if (nl == bytes::npos) break;
      i = nl;
      continue;
    }
    out.push_back(d[i]);
    i += 1;
  }
  return out;
}

bytes t_removecommentschar(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  while (i < n) {
    if (d.compare(i, 2, "/*") == 0) { i += 2; continue; }
    if (d.compare(i, 2, "*/") == 0) { i += 2; continue; }
    if (d.compare(i, 4, "<!--") == 0) { i += 4; continue; }
    if (d.compare(i, 3, "-->") == 0) { i += 3; continue; }
    if (d.compare(i, 2, "--") == 0) { i += 2; continue; }
    if (d[i] == '#') { i += 1; continue; }
    out.push_back(d[i]);
    i += 1;
  }
  return out;
}

bytes normalize_path(const bytes& in, bool win) {
  bytes d = in;
  if (win)
    for (auto& c : d)
      if (c == '\\') c = '/';
  bool leading = !d.empty() && d[0] == '/';
  bool trailing = false;
  {
    auto ends = [&](const char* s) {
      size_t l = strlen(s);
      return d.size() >= l && d.compare(d.size() - l, l, s) == 0;
    };
    trailing = ends("/") || ends("/.") || ends("/..");
  }
  std::vector<bytes> parts;
  size_t i = 0;
  while (i <= d.size()) {
    size_t j = d.find('/', i);
    if (j == bytes::npos) j = d.size();
    bytes seg = d.substr(i, j - i);
    i = j + 1;
    if (seg.empty() || seg == ".") {
      if (j == d.size()) break;
      continue;
    }
    if (seg == "..") {
      if (!parts.empty() && parts.back() != "..")
        parts.pop_back();
      else if (!leading)
        parts.push_back(seg);
    } else {
      parts.push_back(seg);
    }
    if (j == d.size()) break;
  }
  bytes out;
  for (size_t k = 0; k < parts.size(); k++) {
    if (k) out.push_back('/');
    out += parts[k];
  }
  if (leading) out = "/" + out;
  if (trailing && !out.empty() && out.back() != '/') out.push_back('/');
  return out;
}

bytes t_cmdline(const bytes& d) {
  bytes s;
  for (uint8_t c : d) {
    if (c == '\\' || c == '"' || c == '\'' || c == '^') continue;
    if (c == ',' || c == ';') c = ' ';
    s.push_back((char)c);
  }
  bytes out;
  for (uint8_t c : s) {
    if (c == '/' || c == '(') {
      while (!out.empty() && is_ws((uint8_t)out.back())) out.pop_back();
    }
    out.push_back((char)c);
  }
  for (auto& c : out)
    if (c >= 'A' && c <= 'Z') c += 32;
  return t_compresswhitespace(out);
}

bytes t_jsdecode(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  while (i < n) {
    uint8_t c = d[i];
    if (c != '\\' || i + 1 >= n) {
      out.push_back((char)c);
      i += 1;
      continue;
    }
    uint8_t e = d[i + 1];
    if ((e == 'x' || e == 'X') && i + 3 < n && is_hex(d[i + 2]) &&
        is_hex(d[i + 3])) {
      out.push_back((char)(hex_val(d[i + 2]) * 16 + hex_val(d[i + 3])));
      i += 4;
    } else if (e == 'u' && i + 5 < n && is_hex(d[i + 2]) && is_hex(d[i + 3]) &&
               is_hex(d[i + 4]) && is_hex(d[i + 5])) {
      int val = (hex_val(d[i + 2]) << 12) | (hex_val(d[i + 3]) << 8) |
                (hex_val(d[i + 4]) << 4) | hex_val(d[i + 5]);
      out.push_back((char)(val & 0xFF));
      i += 6;
    } else if (e >= '0' && e <= '7') {
      size_t j = i + 1;
      int val = 0;
      while (j < n && d[j] >= '0' && d[j] <= '7' && j - i <= 3) {
        val = val * 8 + (d[j] - '0');
        j++;
      }
      out.push_back((char)(val & 0xFF));
      i = j;
    } else {
      switch (e) {
        case 'a': out.push_back((char)7); break;
        case 'b': out.push_back((char)8); break;
        case 'f': out.push_back((char)12); break;
        case 'n': out.push_back((char)10); break;
        case 'r': out.push_back((char)13); break;
        case 't': out.push_back((char)9); break;
        case 'v': out.push_back((char)11); break;
        default: out.push_back((char)e);
      }
      i += 2;
    }
  }
  return out;
}

bytes t_cssdecode(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  while (i < n) {
    uint8_t c = d[i];
    if (c != '\\' || i + 1 >= n) {
      out.push_back((char)c);
      i += 1;
      continue;
    }
    size_t j = i + 1, start = j;
    while (j < n && is_hex(d[j]) && j - start < 6) j++;
    if (j > start) {
      unsigned long val = strtoul(d.substr(start, j - start).c_str(), nullptr, 16);
      out.push_back((char)(val & 0xFF));
      if (j < n && (d[j] == ' ' || d[j] == '\t' || d[j] == '\n' ||
                    d[j] == '\r' || d[j] == '\f'))
        j++;
      i = j;
    } else {
      out.push_back(d[i + 1]);
      i += 2;
    }
  }
  return out;
}

inline int b64_val(uint8_t c) {
  if (c >= 'A' && c <= 'Z') return c - 'A';
  if (c >= 'a' && c <= 'z') return c - 'a' + 26;
  if (c >= '0' && c <= '9') return c - '0' + 52;
  if (c == '+') return 62;
  if (c == '/') return 63;
  return -1;
}
inline bool b64_char(uint8_t c) { return b64_val(c) >= 0 || c == '='; }

// CPython binascii.a2b_base64 (strict_mode=False) semantics, empirically
// verified: data chars accumulate in quads; '=' at quad position 3 emits 2
// bytes and STOPS (rest ignored); '=' at quad position 2 must be followed
// by another '=' (then emit 1 byte and stop), a data char there is an
// error; '=' at positions 0/1 is an error; end-of-input with a partial
// quad is an error. Errors -> b"" (the Python wrapper catches and returns
// empty). Input contains only alphabet chars and '='.
bytes b64_core(const bytes& data) {
  bytes out;
  uint32_t acc = 0;
  int quad = 0;
  for (size_t i = 0; i < data.size(); i++) {
    uint8_t c = data[i];
    if (c == '=') {
      if (quad == 3) {
        out.push_back((char)((acc >> 10) & 0xFF));
        out.push_back((char)((acc >> 2) & 0xFF));
        return out;
      }
      if (quad == 2) {
        // require the next char to be '='
        if (i + 1 < data.size() && data[i + 1] == '=') {
          out.push_back((char)((acc >> 4) & 0xFF));
          return out;
        }
        return bytes();  // ab=c / trailing single '=' -> Incorrect padding
      }
      return bytes();  // '=' with 0/1 data chars in the quad
    }
    acc = (acc << 6) | (uint32_t)b64_val(c);
    quad++;
    if (quad == 4) {
      out.push_back((char)((acc >> 16) & 0xFF));
      out.push_back((char)((acc >> 8) & 0xFF));
      out.push_back((char)(acc & 0xFF));
      acc = 0;
      quad = 0;
    }
  }
  if (quad != 0) return bytes();  // partial quad at end -> error -> b""
  return out;
}

bytes t_base64decode(const bytes& d) {
  size_t end = 0;
  while (end < d.size() && b64_char((uint8_t)d[end])) end++;
  bytes chunk = d.substr(0, end);
  if (chunk.size() % 4) chunk = chunk.substr(0, chunk.size() - chunk.size() % 4);
  return b64_core(chunk);
}

bytes t_base64decodeext(const bytes& d) {
  bytes filtered;
  for (uint8_t c : d)
    if (b64_char(c) && c != '=') filtered.push_back((char)c);
  while (filtered.size() % 4) filtered.push_back('=');
  return b64_core(filtered);
}

bytes t_base64encode(const bytes& d) {
  static const char* tbl =
      "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/";
  bytes out;
  size_t i = 0;
  while (i + 2 < d.size()) {
    uint32_t v = ((uint8_t)d[i] << 16) | ((uint8_t)d[i + 1] << 8) | (uint8_t)d[i + 2];
    out.push_back(tbl[v >> 18]);
    out.push_back(tbl[(v >> 12) & 63]);
    out.push_back(tbl[(v >> 6) & 63]);
    out.push_back(tbl[v & 63]);
    i += 3;
  }
  size_t rem = d.size() - i;
  if (rem == 1) {
    uint32_t v = (uint8_t)d[i] << 16;
    out.push_back(tbl[v >> 18]);
    out.push_back(tbl[(v >> 12) & 63]);
    out += "==";
  } else if (rem == 2) {
    uint32_t v = ((uint8_t)d[i] << 16) | ((uint8_t)d[i + 1] << 8);
    out.push_back(tbl[v >> 18]);
    out.push_back(tbl[(v >> 12) & 63]);
    out.push_back(tbl[(v >> 6) & 63]);
    out.push_back('=');
  }
  return out;
}

bytes t_hexdecode(const bytes& d) {
  bytes filtered;
  for (uint8_t c : d)
    if (is_hex(c)) filtered.push_back((char)c);
  if (filtered.size() % 2) filtered.pop_back();
  bytes out;
  for (size_t i = 0; i + 1 < filtered.size() || (i + 1 == filtered.size()); i += 2) {
    if (i + 1 >= filtered.size()) break;
    out.push_back((char)(hex_val(filtered[i]) * 16 + hex_val(filtered[i + 1])));
  }
  return out;
}

bytes t_hexencode(const bytes& d) {
  static const char* hx = "0123456789abcdef";
  bytes out;
  out.reserve(d.size() * 2);
  for (uint8_t c : d) {
    out.push_back(hx[c >> 4]);
    out.push_back(hx[c & 15]);
  }
  return out;
}

bytes t_urlencode(const bytes& d) {
  static const char* hx = "0123456789abcdef";
  bytes out;
  for (uint8_t c : d) {
    if ((c >= '0' && c <= '9') || (c >= 'A' && c <= 'Z') ||
        (c >= 'a' && c <= 'z') || c == '-' || c == '_' || c == '.') {
      out.push_back((char)c);
    } else {
      out.push_back('%');
      out.push_back(hx[c >> 4]);
      out.push_back(hx[c & 15]);
    }
  }
  return out;
}

bytes t_utf8tounicode(const bytes& d) {
  static const char* hx = "0123456789abcdef";
  bytes out;
  size_t i = 0, n = d.size();
  auto emit = [&](unsigned cp) {
    // %04x semantics: minimum 4 hex digits, more for cp > 0xFFFF
    char digits[8];
    int nd = 0;
    unsigned v = cp;
    do {
      digits[nd++] = hx[v & 15];
      v >>= 4;
    } while (v);
    while (nd < 4) digits[nd++] = '0';
    out += "%u";
    for (int k = nd - 1; k >= 0; k--) out.push_back(digits[k]);
  };
  while (i < n) {
    uint8_t b = d[i];
    if (b < 0x80) {
      out.push_back((char)b);
      i += 1;
      continue;
    }
    bool done = false;
    // try widths 2,3,4 like the Python reference (strict UTF-8 decode)
    for (int width = 2; width <= 4 && !done; width++) {
      if (i + width > n) continue;
      unsigned cp = 0;
      bool ok = true;
      uint8_t c0 = d[i];
      if (width == 2 && (c0 & 0xE0) == 0xC0) cp = c0 & 0x1F;
      else if (width == 3 && (c0 & 0xF0) == 0xE0) cp = c0 & 0x0F;
      else if (width == 4 && (c0 & 0xF8) == 0xF0) cp = c0 & 0x07;
      else ok = false;
      for (int k = 1; ok && k < width; k++) {
        uint8_t ck = d[i + k];
        if ((ck & 0xC0) != 0x80) ok = false;
        else cp = (cp << 6) | (ck & 0x3F);
      }
      if (!ok) continue;
      // reject overlongs / surrogates / out of range, as strict UTF-8 does
      static const unsigned mins[5] = {0, 0, 0x80, 0x800, 0x10000};
      if (cp < mins[width] || cp > 0x10FFFF || (cp >= 0xD800 && cp <= 0xDFFF))
        continue;
      emit(cp);
      i += width;
      done = true;
    }
    if (!done) {
      out.push_back((char)b);
      i += 1;
    }
  }
  return out;
}

bytes apply_op(uint8_t op, const bytes& d) {
  switch (op) {
    case OP_NONE: return d;
    case OP_LOWERCASE: return lower(d);
    case OP_UPPERCASE: {
      bytes out = d;
      for (auto& c : out)
        if (c >= 'a' && c <= 'z') c -= 32;
      return out;
    }
    case OP_URLDECODE: return t_urldecode(d);
    case OP_URLDECODEUNI: return t_urldecodeuni(d);
    case OP_URLENCODE: return t_urlencode(d);
    case OP_HTMLENTITYDECODE: return t_htmlentitydecode(d);
    case OP_REMOVENULLS: {
      bytes out;
      for (char c : d)
        if (c != 0) out.push_back(c);
      return out;
    }
    case OP_REPLACENULLS: {
      bytes out = d;
      for (auto& c : out)
        if (c == 0) c = ' ';
      return out;
    }
    case OP_REMOVEWHITESPACE: {
      bytes out;
      for (uint8_t c : d)
        if (!is_ws(c)) out.push_back((char)c);
      return out;
    }
    case OP_COMPRESSWHITESPACE: return t_compresswhitespace(d);
    case OP_TRIM: {
      size_t a = 0, b = d.size();
      while (a < b && is_ws((uint8_t)d[a])) a++;
      while (b > a && is_ws((uint8_t)d[b - 1])) b--;
      return d.substr(a, b - a);
    }
    case OP_TRIMLEFT: {
      size_t a = 0;
      while (a < d.size() && is_ws((uint8_t)d[a])) a++;
      return d.substr(a);
    }
    case OP_TRIMRIGHT: {
      size_t b = d.size();
      while (b > 0 && is_ws((uint8_t)d[b - 1])) b--;
      return d.substr(0, b);
    }
    case OP_REMOVECOMMENTS: return t_removecomments(d);
    case OP_REMOVECOMMENTSCHAR: return t_removecommentschar(d);
    case OP_REPLACECOMMENTS: return t_replacecomments(d);
    case OP_NORMALIZEPATH: return normalize_path(d, false);
    case OP_NORMALIZEPATHWIN: return normalize_path(d, true);
    case OP_CMDLINE: return t_cmdline(d);
    case OP_JSDECODE: return t_jsdecode(d);
    case OP_CSSDECODE: return t_cssdecode(d);
    case OP_BASE64DECODE: return t_base64decode(d);
    case OP_BASE64DECODEEXT: return t_base64decodeext(d);
    case OP_BASE64ENCODE: return t_base64encode(d);
    case OP_HEXDECODE: return t_hexdecode(d);
    case OP_HEXENCODE: return t_hexencode(d);
    case OP_ESCAPESEQDECODE: return t_jsdecode(d);
    case OP_UTF8TOUNICODE: return t_utf8tounicode(d);
    case OP_LENGTH: return std::to_string(d.size());
    default: return d;
  }
}

// ---------------------------------------------------------------------------
// minimal JSON (semantics: json.loads over utf-8 'replace'-decoded text,
// flattened like engine/request.py:_flatten_json)
// ---------------------------------------------------------------------------

// Replace invalid UTF-8 with U+FFFD (EF BF BD), like bytes.decode('utf-8',
// 'replace'), so string content matches the Python path byte-for-byte.
bytes utf8_replace(const bytes& d) {
  bytes out;
  size_t i = 0, n = d.size();
  auto bad = [&](size_t adv) {
    out += "\xEF\xBF\xBD";
    i += adv;
  };
  while (i < n) {
    uint8_t c = d[i];
    if (c < 0x80) {
      out.push_back((char)c);
      i++;
    } else if ((c & 0xE0) == 0xC0) {
      if (c < 0xC2 || i + 1 >= n || ((uint8_t)d[i + 1] & 0xC0) != 0x80) bad(1);
      else {
        out += d.substr(i, 2);
        i += 2;
      }
    } else if ((c & 0xF0) == 0xE0) {
      uint8_t lo = 0x80, hi = 0xBF;
      if (c == 0xE0) lo = 0xA0;
      if (c == 0xED) hi = 0x9F;
      if (i + 1 >= n || (uint8_t)d[i + 1] < lo || (uint8_t)d[i + 1] > hi) bad(1);
      else if (i + 2 >= n || ((uint8_t)d[i + 2] & 0xC0) != 0x80) bad(2);
      else {
        out += d.substr(i, 3);
        i += 3;
      }
    } else if ((c & 0xF8) == 0xF0 && c <= 0xF4) {
      uint8_t lo = 0x80, hi = 0xBF;
      if (c == 0xF0) lo = 0x90;
      if (c == 0xF4) hi = 0x8F;
      if (i + 1 >= n || (uint8_t)d[i + 1] < lo || (uint8_t)d[i + 1] > hi) bad(1);
      else if (i + 2 >= n || ((uint8_t)d[i + 2] & 0xC0) != 0x80) bad(2);
      else if (i + 3 >= n || ((uint8_t)d[i + 3] & 0xC0) != 0x80) bad(3);
      else {
        out += d.substr(i, 4);
        i += 4;
      }
    } else {
      bad(1);
    }
  }
  return out;
}

void append_utf8(bytes& out, unsigned cp) {
  if (cp < 0x80) out.push_back((char)cp);
  else if (cp < 0x800) {
    out.push_back((char)(0xC0 | (cp >> 6)));
    out.push_back((char)(0x80 | (cp & 0x3F)));
  } else if (cp < 0x10000) {
    out.push_back((char)(0xE0 | (cp >> 12)));
    out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
    out.push_back((char)(0x80 | (cp & 0x3F)));
  } else {
    out.push_back((char)(0xF0 | (cp >> 18)));
    out.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
    out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
    out.push_back((char)(0x80 | (cp & 0x3F)));
  }
}

struct JsonParser {
  const bytes& s;
  size_t i = 0;
  bool ok = true;
  std::vector<std::pair<bytes, bytes>>* out;
  int depth = 0;

  explicit JsonParser(const bytes& text, std::vector<std::pair<bytes, bytes>>* o)
      : s(text), out(o) {}

  void ws() {
    while (i < s.size() && (s[i] == ' ' || s[i] == '\t' || s[i] == '\n' || s[i] == '\r'))
      i++;
  }
  bool lit(const char* word) {
    size_t l = strlen(word);
    if (s.compare(i, l, word) == 0) {
      i += l;
      return true;
    }
    return false;
  }

  bool parse_string(bytes& dest) {
    if (i >= s.size() || s[i] != '"') return false;
    i++;
    while (i < s.size()) {
      uint8_t c = s[i];
      if (c == '"') {
        i++;
        return true;
      }
      if (c == '\\') {
        if (i + 1 >= s.size()) return false;
        uint8_t e = s[i + 1];
        i += 2;
        switch (e) {
          case '"': dest.push_back('"'); break;
          case '\\': dest.push_back('\\'); break;
          case '/': dest.push_back('/'); break;
          case 'b': dest.push_back('\b'); break;
          case 'f': dest.push_back('\f'); break;
          case 'n': dest.push_back('\n'); break;
          case 'r': dest.push_back('\r'); break;
          case 't': dest.push_back('\t'); break;
          case 'u': {
            if (i + 4 > s.size()) return false;
            unsigned cp = 0;
            for (int k = 0; k < 4; k++) {
              if (!is_hex(s[i + k])) return false;
              cp = (cp << 4) | hex_val(s[i + k]);
            }
            i += 4;
            if (cp >= 0xD800 && cp <= 0xDBFF && i + 6 <= s.size() &&
                s[i] == '\\' && s[i + 1] == 'u') {
              unsigned lo2 = 0;
              bool okh = true;
              for (int k = 0; k < 4; k++) {
                if (!is_hex(s[i + 2 + k])) { okh = false; break; }
                lo2 = (lo2 << 4) | hex_val(s[i + 2 + k]);
              }
              if (okh && lo2 >= 0xDC00 && lo2 <= 0xDFFF) {
                cp = 0x10000 + ((cp - 0xD800) << 10) + (lo2 - 0xDC00);
                i += 6;
              }
            }
            append_utf8(dest, cp);
            break;
          }
          default: return false;
        }
        continue;
      }
      if (c < 0x20) return false;  // control chars invalid (strict=True)
      dest.push_back((char)c);
      i++;
    }
    return false;
  }

  // number token -> Python-compatible string rendering
  bool parse_number(bytes& dest) {
    size_t start = i;
    if (i < s.size() && s[i] == '-') i++;
    if (i >= s.size() || s[i] < '0' || s[i] > '9') return false;
    while (i < s.size() && s[i] >= '0' && s[i] <= '9') i++;
    bool is_float = false;
    if (i < s.size() && s[i] == '.') {
      is_float = true;
      i++;
      if (i >= s.size() || s[i] < '0' || s[i] > '9') return false;
      while (i < s.size() && s[i] >= '0' && s[i] <= '9') i++;
    }
    if (i < s.size() && (s[i] == 'e' || s[i] == 'E')) {
      is_float = true;
      i++;
      if (i < s.size() && (s[i] == '+' || s[i] == '-')) i++;
      if (i >= s.size() || s[i] < '0' || s[i] > '9') return false;
      while (i < s.size() && s[i] >= '0' && s[i] <= '9') i++;
    }
    bytes tok = s.substr(start, i - start);
    if (!is_float) {
      // Python str(int(tok)): strip leading zeros (invalid JSON anyway),
      // normalize -0 -> 0
      if (tok == "-0") dest = "0";
      else dest = tok;
      return true;
    }
    double v = strtod(tok.c_str(), nullptr);
    // Python repr(float): shortest round-trip, with '.0' for integral
    char buf[64];
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
    auto res = std::to_chars(buf, buf + sizeof buf, v);
    bytes r(buf, res.ptr - buf);
#else
    // libstdc++ < 11 ships integer-only to_chars: probe precisions for
    // the shortest %g rendering that round-trips (same switch-to-
    // exponent thresholds as Python repr).
    for (int prec = 1; prec <= 17; prec++) {
      snprintf(buf, sizeof buf, "%.*g", prec, v);
      if (strtod(buf, nullptr) == v) break;
    }
    bytes r(buf);
#endif
    if (r.find('.') == bytes::npos && r.find('e') == bytes::npos &&
        r.find("inf") == bytes::npos && r.find("nan") == bytes::npos)
      r += ".0";
    // std::to_chars writes 1e+30 as "1e+30"? It writes "1e+30"; Python too.
    dest = r;
    return true;
  }

  bool value(const bytes& prefix) {
    if (++depth > 500) return false;  // Python RecursionError analog
    ws();
    if (i >= s.size()) return false;
    uint8_t c = s[i];
    bool result;
    if (c == '{') {
      i++;
      ws();
      if (i < s.size() && s[i] == '}') {
        i++;
        result = true;
      } else {
        result = true;
        while (true) {
          ws();
          bytes key;
          if (!parse_string(key)) { result = false; break; }
          ws();
          if (i >= s.size() || s[i] != ':') { result = false; break; }
          i++;
          bytes child = prefix.empty() ? key : prefix + "." + key;
          if (!value(child)) { result = false; break; }
          ws();
          if (i < s.size() && s[i] == ',') { i++; continue; }
          if (i < s.size() && s[i] == '}') { i++; break; }
          result = false;
          break;
        }
      }
    } else if (c == '[') {
      i++;
      ws();
      if (i < s.size() && s[i] == ']') {
        i++;
        result = true;
      } else {
        result = true;
        int idx = 0;
        while (true) {
          bytes child = prefix.empty() ? std::to_string(idx)
                                       : prefix + "." + std::to_string(idx);
          if (!value(child)) { result = false; break; }
          idx++;
          ws();
          if (i < s.size() && s[i] == ',') { i++; continue; }
          if (i < s.size() && s[i] == ']') { i++; break; }
          result = false;
          break;
        }
      }
    } else if (c == '"') {
      bytes v2;
      result = parse_string(v2);
      if (result) out->emplace_back(prefix, v2);
    } else if (lit("true")) {
      out->emplace_back(prefix, "true");
      result = true;
    } else if (lit("false")) {
      out->emplace_back(prefix, "false");
      result = true;
    } else if (lit("null")) {
      out->emplace_back(prefix, "");
      result = true;
    } else {
      bytes num;
      result = parse_number(num);
      if (result) out->emplace_back(prefix, num);
    }
    depth--;
    return result;
  }

  bool parse_document() {
    bool okv = value("json");
    ws();
    return okv && i == s.size();
  }
};

// ---------------------------------------------------------------------------
// DFA (selector regex kinds) — semantics: compiler/re_dfa.py DFA.search
// ---------------------------------------------------------------------------

struct Dfa {
  uint32_t S = 0, C = 0;
  bool always = false;
  std::vector<uint16_t> classmap;  // [256]
  std::vector<uint32_t> trans;     // [S*C]
  std::vector<uint8_t> emit;       // [S*C]
  std::vector<uint8_t> match_end;  // [S]

  bool search(const bytes& data) const {
    if (always) return true;
    uint32_t s = 0;
    for (uint8_t b : data) {
      uint32_t c = classmap[b];
      if (emit[s * C + c]) return true;
      s = trans[s * C + c];
    }
    return match_end[s];
  }
};

// ---------------------------------------------------------------------------
// context / config
// ---------------------------------------------------------------------------

// Collections the extractor generates (order shared with the Python
// serializer).
enum Coll : uint8_t {
  C_ARGS = 0, C_ARGS_GET, C_ARGS_POST, C_ARGS_NAMES, C_ARGS_GET_NAMES,
  C_ARGS_POST_NAMES, C_REQUEST_HEADERS, C_REQUEST_HEADERS_NAMES,
  C_REQUEST_COOKIES, C_REQUEST_COOKIES_NAMES, C_FILES, C_FILES_NAMES,
  C_COUNT_
};

// Scalar targets in the exact order of engine/request.py `scalars` dict.
enum ScalarId : uint8_t {
  S_REQUEST_URI = 0, S_REQUEST_URI_RAW, S_REQUEST_FILENAME,
  S_REQUEST_BASENAME, S_REQUEST_LINE, S_REQUEST_METHOD, S_REQUEST_PROTOCOL,
  S_QUERY_STRING, S_REQUEST_BODY, S_FULL_REQUEST, S_PATH_INFO, S_REMOTE_ADDR,
  S_SERVER_NAME, S_STATUS_LINE, S_RESPONSE_BODY, S_AUTH_TYPE,
  S_REQBODY_PROCESSOR,
  S_COUNT_
};

// Numeric values in the exact order of `numeric_values`.
enum NumId : uint8_t {
  N_REQUEST_BODY_LENGTH = 0, N_REQBODY_ERROR, N_MULTIPART_STRICT_ERROR,
  N_MULTIPART_UNMATCHED_BOUNDARY, N_ARGS_COMBINED_SIZE,
  N_FULL_REQUEST_LENGTH, N_FILES_COMBINED_SIZE, N_RESPONSE_STATUS, N_DURATION,
  N_COUNT_
};

struct KindKey {
  uint8_t coll;
  bytes sel;  // lowercased; empty = generic
  bool operator==(const KindKey& o) const {
    return coll == o.coll && sel == o.sel;
  }
};
struct KindKeyHash {
  size_t operator()(const KindKey& k) const {
    return std::hash<bytes>()(k.sel) * 31 + k.coll;
  }
};

struct RegexKind {
  uint8_t coll;
  uint32_t kind;
  Dfa dfa;
};

struct NumVarSpec {
  uint8_t type;  // 0 scalar, 1 count, 2 host op (sqli/xss)
  uint8_t scalar_id = 0;
  uint8_t coll = 0;
  bool has_sel = false;
  bytes sel;  // lowercased
  // type == 2 (host-evaluated operator over transformed targets):
  uint8_t op_id = 0;  // 0 = sqli (libinjection-architecture)
  std::vector<uint8_t> pipe_ops;           // transform chain
  std::vector<uint8_t> inc_kinds, exc_kinds;  // bitmasks over kind ids
};

// ---------------------------------------------------------------------------
// libinjection-architecture SQLi machine (compiler/sqli.py port).
// The word-class map and fingerprint table arrive IN the config blob —
// generated by the Python module, so table and tokenizer can never
// skew; the tokenizer itself is differentially tested via cko_sqli().
// ---------------------------------------------------------------------------

struct SqliTables {
  std::unordered_map<bytes, char> words;  // lowercased word -> type char
  std::unordered_set<bytes> fps;          // folded 5-type fingerprints
};

static inline bool sq_space(char c) {
  return c == ' ' || c == '\t' || c == '\n' || c == '\r' || c == '\v' ||
         c == '\f';
}
static inline bool sq_digit(char c) { return c >= '0' && c <= '9'; }
static inline bool sq_hexd(char c) {
  return sq_digit(c) || (c >= 'a' && c <= 'f') || (c >= 'A' && c <= 'F');
}
static inline bool sq_opchar(char c) {
  switch (c) {
    case '+': case '-': case '*': case '/': case '%': case '=': case '<':
    case '>': case '!': case '^': case '~': case '|': case '&': case ':':
      return true;
    default:
      return false;
  }
}
static inline bool sq_wordchar(char c) {
  return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') || sq_digit(c) ||
         c == '_' || c == '$' || c == '.' || c == '@' || c == '#';
}

static char sq_classify(const SqliTables& T, const bytes& word) {
  bytes lw = lower(word);
  size_t a = 0, b = lw.size();
  while (a < b && lw[a] == '.') a++;
  while (b > a && lw[b - 1] == '.') b--;
  auto it = T.words.find(lw.substr(a, b - a));
  return it == T.words.end() ? 'v' : it->second;
}

// Exact port of compiler/sqli.py:tokenize (type chars only — fold reads
// nothing else; '&&'/'||' classification happens here as in Python).
static void sq_tokenize(const SqliTables& T, const bytes& s,
                        std::string& out) {
  size_t i = 0, n = s.size();
  size_t emitted0 = out.size();
  while (i < n && out.size() - emitted0 < 32) {
    char c = s[i];
    if (sq_space(c)) { i++; continue; }
    if ((c == '-' && i + 1 < n && s[i + 1] == '-') || c == '#') {
      out.push_back('c');
      break;
    }
    if (c == '/' && i + 1 < n && s[i + 1] == '*') {
      size_t end = s.find("*/", i + 2);
      if (end == bytes::npos) { out.push_back('c'); break; }
      if (i + 2 < n && s[i + 2] == '!') {
        bytes body = s.substr(i + 3, end - (i + 3));
        size_t k = 0;
        while (k < body.size() && sq_digit(body[k])) k++;
        sq_tokenize(T, body.substr(k), out);
      }
      i = end + 2;
      continue;
    }
    if (c == '\'' || c == '"' || c == '`') {
      size_t j = i + 1;
      while (j < n) {
        if (s[j] == '\\') { j += 2; continue; }
        if (s[j] == c) break;
        j++;
      }
      out.push_back('s');
      i = j + 1;
      continue;
    }
    if (sq_digit(c) || (c == '.' && i + 1 < n && sq_digit(s[i + 1]))) {
      size_t j = i;
      if (c == '0' && i + 1 < n && (s[i + 1] == 'x' || s[i + 1] == 'X')) {
        j = i + 2;
        while (j < n && sq_hexd(s[j])) j++;
      } else {
        while (j < n && (sq_digit(s[j]) || s[j] == '.' || s[j] == 'e' ||
                         s[j] == 'E'))
          j++;
      }
      out.push_back('n');
      i = j;
      continue;
    }
    if (c == '(' || c == ')' || c == ',' || c == ';') {
      out.push_back(c);
      i++;
      continue;
    }
    if (c == '@') {
      size_t j = i;
      while (j < n && (s[j] == '@' || sq_wordchar(s[j]))) j++;
      out.push_back('v');
      i = j;
      continue;
    }
    if (sq_opchar(c)) {
      size_t j = i;
      while (j < n && sq_opchar(s[j]) && j - i < 3) j++;
      bytes text = s.substr(i, j - i);
      out.push_back(text == "&&" || text == "||" ? '&' : 'o');
      i = j;
      continue;
    }
    if (sq_wordchar(c)) {
      size_t j = i;
      while (j < n && sq_wordchar(s[j])) j++;
      out.push_back(sq_classify(T, s.substr(i, j - i)));
      i = j;
      continue;
    }
    out.push_back('x');
    i++;
  }
}

static std::string sq_fold(const std::string& types) {
  std::string out;
  for (char t : types) {
    if (!out.empty()) {
      char prev = out.back();
      if (t == prev && (t == 'v' || t == 's' || t == 'c')) continue;
      if (t == 'o' && prev == 'o') continue;
    }
    out.push_back(t);
  }
  return out;
}

// ---------------------------------------------------------------------------
// libinjection-architecture XSS machine (compiler/xss.py port): html5
// walk in five injection contexts, danger tables from the config blob.
// ---------------------------------------------------------------------------

struct XssTables {
  std::unordered_set<bytes> tags;    // lowercased blacklisted tag names
  std::unordered_set<bytes> attrs;   // lowercased blacklisted attr names
  std::vector<bytes> schemes;        // lowercased dangerous URL schemes
};

static inline bool xs_space(char c) {
  return c == ' ' || c == '\t' || c == '\n' || c == '\r' || c == '\v' ||
         c == '\f';
}
static inline bool xs_alpha(char c) {
  return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z');
}
static inline bool xs_alnum(char c) { return xs_alpha(c) || (c >= '0' && c <= '9'); }

static bool xs_black_url(const XssTables& T, const bytes& value) {
  bytes stripped;
  for (char c : value)
    if ((unsigned char)c > 0x20) stripped.push_back(c);
  stripped = lower(stripped);
  for (const bytes& sc : T.schemes)
    if (stripped.size() >= sc.size() && stripped.compare(0, sc.size(), sc) == 0)
      return true;
  return false;
}

static bool xs_attr_danger(const XssTables& T, const bytes& name, const bytes& value) {
  bytes ln = lower(name);
  while (!ln.empty() && xs_space(ln.back())) ln.pop_back();
  if (ln.size() > 2 && ln[0] == 'o' && ln[1] == 'n') return true;
  if (T.attrs.count(ln)) return true;
  if (!value.empty() && xs_black_url(T, value)) return true;
  return false;
}

// Returns: 0 = clean (end of input), 1 = dangerous, else resume index + 2.
static long xs_scan_in_tag(const XssTables& T, const bytes& s, size_t i) {
  size_t n = s.size();
  while (i < n) {
    while (i < n && (xs_space(s[i]) || s[i] == '/')) i++;
    if (i >= n) return 0;
    if (s[i] == '>') return (long)(i + 1) + 2;
    size_t a0 = i;
    while (i < n && !xs_space(s[i]) && s[i] != '=' && s[i] != '>' && s[i] != '/')
      i++;
    bytes name = s.substr(a0, i - a0);
    while (i < n && xs_space(s[i])) i++;
    bytes value;
    if (i < n && s[i] == '=') {
      i++;
      while (i < n && xs_space(s[i])) i++;
      if (i < n && (s[i] == '\'' || s[i] == '"' || s[i] == '`')) {
        char q = s[i];
        size_t v0 = i + 1;
        size_t vend = s.find(q, v0);
        if (vend == bytes::npos) {
          value = s.substr(v0);
          i = n;
        } else {
          value = s.substr(v0, vend - v0);
          i = vend + 1;
        }
      } else {
        size_t v0 = i;
        while (i < n && !xs_space(s[i]) && s[i] != '>') i++;
        value = s.substr(v0, i - v0);
      }
    }
    if (!name.empty() && xs_attr_danger(T, name, value)) return 1;
  }
  return 0;
}

static bool xs_scan_data(const XssTables& T, const bytes& s, size_t i) {
  size_t n = s.size();
  while (i < n) {
    size_t lt = s.find('<', i);
    if (lt == bytes::npos) return false;
    i = lt + 1;
    if (i >= n) return false;
    char c = s[i];
    if (c == '!') {
      bytes rest = lower(s.substr(i + 1, 9));
      if (rest.rfind("entity", 0) == 0 || s.substr(i + 1, 4) == "--[i" ||
          rest.rfind("[cdata", 0) == 0)
        return true;
      if (s.compare(i + 1, 2, "--") == 0) {
        size_t end = s.find("-->", i + 3);
        if (end == bytes::npos) return false;
        i = end + 3;
        continue;
      }
      continue;
    }
    if (c == '/') { i++; continue; }
    if (!xs_alpha(c)) continue;
    size_t j = i;
    while (j < n && (xs_alnum(s[j]) || s[j] == '-' || s[j] == ':')) j++;
    bytes tag = lower(s.substr(i, j - i));
    if (T.tags.count(tag)) return true;
    long res = xs_scan_in_tag(T, s, j);
    if (res == 1) return true;
    if (res == 0) return false;
    i = (size_t)(res - 2);
  }
  return false;
}

static bool xs_scan(const XssTables& T, const bytes& s, int ctx) {
  size_t i = 0, n = s.size();
  if (ctx != 0) {
    char closer = ctx == 2 ? '\'' : ctx == 3 ? '"' : ctx == 4 ? '`' : 0;
    size_t val_start = i;
    while (i < n) {
      char c = s[i];
      if (closer != 0 && c == closer) break;
      if (closer == 0 && (xs_space(c) || c == '>')) break;
      i++;
    }
    if (xs_black_url(T, s.substr(val_start, i - val_start))) return true;
    if (i >= n) return false;
    if (s[i] == '>') return xs_scan_data(T, s, i + 1);
    i++;
    long res = xs_scan_in_tag(T, s, i);
    if (res == 1) return true;
    if (res == 0) return false;
    return xs_scan_data(T, s, (size_t)(res - 2));
  }
  return xs_scan_data(T, s, 0);
}

static bool xs_is_xss(const XssTables& T, const bytes& value) {
  if (value.find('<') == bytes::npos && value.find('=') == bytes::npos &&
      value.find(':') == bytes::npos && value.find('`') == bytes::npos &&
      value.find('\'') == bytes::npos && value.find('"') == bytes::npos)
    return false;
  for (int ctx = 0; ctx < 5; ctx++)
    if (xs_scan(T, value, ctx)) return true;
  return false;
}

static bool sq_is_sqli(const SqliTables& T, const bytes& value) {
  if (value.size() < 3) return false;
  const bytes ctxs[3] = {value, "'" + value, "\"" + value};
  for (const bytes& ctx : ctxs) {
    std::string types;
    sq_tokenize(T, ctx, types);
    std::string fp = sq_fold(types).substr(0, 5);
    if (!fp.empty() && T.fps.count(fp)) return true;
  }
  return false;
}

struct Pipeline {
  std::vector<uint8_t> ops;
  std::vector<uint8_t> kind_member;  // bitmask indexed by kind id
};

struct Ctx {
  bool body_access = false;
  uint32_t body_limit = 0;
  uint32_t n_kinds = 0;
  std::unordered_map<KindKey, uint32_t, KindKeyHash> kinds;
  std::vector<std::vector<RegexKind*>> regex_by_coll;  // per Coll
  std::vector<std::unique_ptr<RegexKind>> regex_kinds;
  uint32_t scalar_kind[S_COUNT_] = {0};
  uint32_t numeric_kind[N_COUNT_] = {0};
  std::vector<Pipeline> pipelines;  // host pipelines in slot order
  std::vector<NumVarSpec> numvars;
  bool has_hostops = false;
  SqliTables sqli;
  XssTables xss;
};

struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  uint8_t u8() {
    if (p + 1 > end) { ok = false; return 0; }
    return *p++;
  }
  uint16_t u16() {
    if (p + 2 > end) { ok = false; return 0; }
    uint16_t v;
    memcpy(&v, p, 2);
    p += 2;
    return v;
  }
  uint32_t u32() {
    if (p + 4 > end) { ok = false; return 0; }
    uint32_t v;
    memcpy(&v, p, 4);
    p += 4;
    return v;
  }
  bytes str() {
    uint32_t l = u32();
    if (!ok || p + l > end) { ok = false; return bytes(); }
    bytes s((const char*)p, l);
    p += l;
    return s;
  }
};

// --- multipart/form-data (engine/request.py:_parse_multipart parity) ---

struct MultipartOut {
  std::vector<std::pair<bytes, bytes>> args;
  std::vector<std::pair<bytes, bytes>> files;  // (field, filename)
  long long files_size = 0;
  int strict_error = 0;
  int unmatched = 0;
};

static size_t find_ci(const bytes& hay, const bytes& needle, size_t from = 0) {
  bytes h = lower(hay), n = lower(needle);
  return h.find(n, from);
}

static MultipartOut parse_multipart(const bytes& content_type, const bytes& body) {
  MultipartOut out;
  // boundary="?([^";,]{1,256})"? case-insensitive, leftmost
  size_t bpos = find_ci(content_type, "boundary=");
  if (bpos == bytes::npos) { out.strict_error = 1; return out; }
  size_t v = bpos + 9;
  bool quoted = v < content_type.size() && content_type[v] == '"';
  if (quoted) v++;
  size_t e = v;
  while (e < content_type.size() && e - v < 256) {
    char c = content_type[e];
    if (c == '"' || c == ';' || c == ',') break;
    e++;
  }
  if (e == v) { out.strict_error = 1; return out; }
  bytes delim = "--" + content_type.substr(v, e - v);

  // split by delim
  std::vector<bytes> segs;
  size_t pos = 0;
  while (true) {
    size_t at = body.find(delim, pos);
    if (at == bytes::npos) { segs.push_back(body.substr(pos)); break; }
    segs.push_back(body.substr(pos, at - pos));
    pos = at + delim.size();
  }
  bytes tail = body;
  size_t te = tail.size();
  while (te > 0 && (tail[te - 1] == '\r' || tail[te - 1] == '\n' || tail[te - 1] == ' '))
    te--;
  bytes closing = delim + "--";
  bool closed = te >= closing.size() &&
                tail.compare(te - closing.size(), closing.size(), closing) == 0;
  if (segs.size() < 2 || !closed) out.strict_error = 1;

  for (size_t si = 1; si < segs.size(); si++) {
    const bytes& seg = segs[si];
    if (seg.rfind("--", 0) == 0) break;  // closing delimiter
    if (!(seg.rfind("\r\n", 0) == 0 || seg.rfind("\n", 0) == 0)) {
      out.strict_error = 1;
      continue;
    }
    size_t s0 = 0;
    while (s0 < seg.size() && (seg[s0] == '\r' || seg[s0] == '\n')) s0++;
    bytes part = seg.substr(s0);
    size_t hsep = part.find("\r\n\r\n");
    size_t clen = 4;
    if (hsep == bytes::npos) { hsep = part.find("\n\n"); clen = 2; }
    if (hsep == bytes::npos) { out.strict_error = 1; continue; }
    bytes head = part.substr(0, hsep);
    bytes content = part.substr(hsep + clen);
    if (content.size() >= 2 && content.compare(content.size() - 2, 2, "\r\n") == 0)
      content.resize(content.size() - 2);
    else
      while (!content.empty() && content.back() == '\n') content.pop_back();
    // content-disposition\s*:\s*form-data\s*; ([^\r\n]*)  (case-insensitive,
    // leftmost MATCH — retry later occurrences like re.search does, so a
    // decoy header merely CONTAINING the substring cannot shadow the real
    // one)
    bool disp_ok = false;
    bytes disp;
    for (size_t dp = find_ci(head, "content-disposition"); dp != bytes::npos;
         dp = find_ci(head, "content-disposition", dp + 1)) {
      size_t q = dp + 19;
      while (q < head.size() && xs_space(head[q])) q++;  // \s* (incl CRLF)
      if (q >= head.size() || head[q] != ':') continue;
      q++;
      while (q < head.size() && xs_space(head[q])) q++;
      if (find_ci(head.substr(q, 9), "form-data") != 0) continue;
      q += 9;
      while (q < head.size() && xs_space(head[q])) q++;
      if (q >= head.size() || head[q] != ';') continue;
      q++;
      size_t le = q;
      while (le < head.size() && head[le] != '\r' && head[le] != '\n') le++;
      disp = head.substr(q, le - q);
      disp_ok = true;
      break;
    }
    if (!disp_ok) { out.strict_error = 1; continue; }
    // leftmost name="..." (matches inside filename=" too — python parity)
    bytes name;
    size_t np = disp.find("name=\"");
    bool has_name = np != bytes::npos;
    if (has_name) {
      size_t ne = disp.find('"', np + 6);
      if (ne != bytes::npos) name = disp.substr(np + 6, ne - (np + 6));
      else has_name = false;
    }
    if (!has_name) out.strict_error = 1;
    size_t fp = disp.find("filename=\"");
    if (fp != bytes::npos) {
      size_t fe = disp.find('"', fp + 10);
      bytes fname = fe == bytes::npos ? bytes() : disp.substr(fp + 10, fe - (fp + 10));
      out.files.emplace_back(name, fname);
      out.files_size += (long long)content.size();
    } else {
      out.args.emplace_back(name, content);
    }
  }

  // boundary-looking lines that are not the declared boundary. Parity
  // with the Python extractor's tightened heuristic: only '--' + RFC
  // 2046 bchars (no spaces), with at least one alphanumeric after the
  // dashes, counts as a delimiter candidate — PEM headers / markdown
  // rules / '--prose' with spaces never trip it.
  auto is_bchar = [](unsigned char c) {
    return std::isalnum(c) || c == '\'' || c == '(' || c == ')' || c == '+' ||
           c == '_' || c == ',' || c == '-' || c == '.' || c == '/' ||
           c == ':' || c == '=' || c == '?';
  };
  size_t lp = 0;
  while (lp <= body.size()) {
    size_t nl = body.find('\n', lp);
    bytes line = body.substr(lp, nl == bytes::npos ? bytes::npos : nl - lp);
    while (!line.empty() && line.back() == '\r') line.pop_back();
    size_t ls = 0;
    while (ls < line.size() && line[ls] == '\r') ls++;
    if (ls) line = line.substr(ls);
    bool starts_delim =
        line.size() >= delim.size() && line.compare(0, delim.size(), delim) == 0;
    if (line.rfind("--", 0) == 0 && line.size() > 4 && line.size() <= 2 + 72 &&
        !starts_delim) {
      bool all_bchars = true;
      bool has_alnum = false;
      for (size_t ci = 2; ci < line.size(); ci++) {
        unsigned char c = static_cast<unsigned char>(line[ci]);
        if (!is_bchar(c)) { all_bchars = false; break; }
        if (std::isalnum(c)) has_alnum = true;
      }
      if (all_bchars && has_alnum) {
        out.unmatched = 1;
        break;
      }
    }
    if (nl == bytes::npos) break;
    lp = nl + 1;
  }
  return out;
}

// row produced by extraction
struct Row {
  int req;
  bytes value;          // truncated to body_cap
  int32_t kinds[3];
  std::vector<bytes> variants;  // per host pipeline (empty when untouched)
};

struct Result {
  std::vector<Row> rows;
  std::vector<std::vector<int32_t>> numvals;  // [n_req][NV]
  size_t max_len = 1;
  // Requests with a body, by the processor that read it (JSON,
  // URLENCODED, MULTIPART, none), their body bytes as received, and how
  // many of them the processor could not parse (REQBODY_ERROR).
  long long bodies[6] = {0, 0, 0, 0, 0, 0};
};

// target scratch (before kind packing)
struct Target {
  uint8_t coll;      // Coll or 0xFF for scalar
  uint8_t scalar_id; // valid when coll == 0xFF
  bytes name;        // selector (original case)
  bytes value;
};

}  // namespace

extern "C" {

void* cko_ctx_new(const uint8_t* blob, size_t len) {
  Reader r{blob, blob + len};
  auto ctx = std::make_unique<Ctx>();
  ctx->body_access = r.u32() != 0;
  ctx->body_limit = r.u32();
  ctx->n_kinds = r.u32();

  uint32_t n_entries = r.u32();
  for (uint32_t i = 0; i < n_entries && r.ok; i++) {
    KindKey k;
    k.coll = r.u8();
    uint16_t sl = r.u16();
    if (r.p + sl > r.end) { r.ok = false; break; }
    k.sel = bytes((const char*)r.p, sl);
    r.p += sl;
    uint32_t kind = r.u32();
    ctx->kinds[k] = kind;
  }

  ctx->regex_by_coll.resize(C_COUNT_);
  uint32_t n_regex = r.u32();
  for (uint32_t i = 0; i < n_regex && r.ok; i++) {
    auto rk = std::make_unique<RegexKind>();
    rk->coll = r.u8();
    rk->kind = r.u32();
    rk->dfa.S = r.u32();
    rk->dfa.C = r.u32();
    rk->dfa.always = r.u8() != 0;
    rk->dfa.classmap.resize(256);
    for (int b = 0; b < 256; b++) rk->dfa.classmap[b] = r.u16();
    size_t sc = (size_t)rk->dfa.S * rk->dfa.C;
    rk->dfa.trans.resize(sc);
    for (size_t j = 0; j < sc; j++) rk->dfa.trans[j] = r.u32();
    rk->dfa.emit.resize(sc);
    for (size_t j = 0; j < sc; j++) rk->dfa.emit[j] = r.u8();
    rk->dfa.match_end.resize(rk->dfa.S);
    for (size_t j = 0; j < rk->dfa.S; j++) rk->dfa.match_end[j] = r.u8();
    if (rk->coll < C_COUNT_) ctx->regex_by_coll[rk->coll].push_back(rk.get());
    ctx->regex_kinds.push_back(std::move(rk));
  }

  for (int i = 0; i < S_COUNT_; i++) ctx->scalar_kind[i] = r.u32();
  for (int i = 0; i < N_COUNT_; i++) ctx->numeric_kind[i] = r.u32();

  uint32_t n_pipes = r.u32();
  for (uint32_t i = 0; i < n_pipes && r.ok; i++) {
    Pipeline p;
    uint32_t n_ops = r.u32();
    for (uint32_t j = 0; j < n_ops; j++) p.ops.push_back(r.u8());
    p.kind_member.assign(ctx->n_kinds + 1, 0);
    uint32_t n_members = r.u32();
    for (uint32_t j = 0; j < n_members; j++) {
      uint32_t kid = r.u32();
      if (kid < p.kind_member.size()) p.kind_member[kid] = 1;
    }
    ctx->pipelines.push_back(std::move(p));
  }

  uint32_t n_nv = r.u32();
  for (uint32_t i = 0; i < n_nv && r.ok; i++) {
    NumVarSpec nv;
    nv.type = r.u8();
    if (nv.type == 0) {
      nv.scalar_id = r.u8();
    } else if (nv.type == 1) {
      nv.coll = r.u8();
      nv.has_sel = r.u8() != 0;
      uint16_t sl = r.u16();
      if (r.p + sl > r.end) { r.ok = false; break; }
      nv.sel = bytes((const char*)r.p, sl);
      r.p += sl;
    } else {  // type 2: host-evaluated operator (sqli)
      nv.op_id = r.u8();
      uint32_t n_ops = r.u32();
      for (uint32_t j = 0; j < n_ops && r.ok; j++)
        nv.pipe_ops.push_back(r.u8());
      nv.inc_kinds.assign(ctx->n_kinds + 1, 0);
      nv.exc_kinds.assign(ctx->n_kinds + 1, 0);
      uint32_t n_inc = r.u32();
      for (uint32_t j = 0; j < n_inc && r.ok; j++) {
        uint32_t kid = r.u32();
        if (kid < nv.inc_kinds.size()) nv.inc_kinds[kid] = 1;
      }
      uint32_t n_exc = r.u32();
      for (uint32_t j = 0; j < n_exc && r.ok; j++) {
        uint32_t kid = r.u32();
        if (kid < nv.exc_kinds.size()) nv.exc_kinds[kid] = 1;
      }
      ctx->has_hostops = true;
    }
    ctx->numvars.push_back(std::move(nv));
  }

  // SQLi tables (present iff any hostop entry exists): word-class map +
  // fingerprint set, generated by compiler/sqli.py.
  if (ctx->has_hostops && r.ok) {
    uint32_t n_words = r.u32();
    for (uint32_t i = 0; i < n_words && r.ok; i++) {
      uint16_t wl = r.u16();
      if (r.p + wl + 1 > r.end) { r.ok = false; break; }
      bytes w((const char*)r.p, wl);
      r.p += wl;
      ctx->sqli.words[w] = (char)r.u8();
    }
    uint32_t n_fps = r.u32();
    for (uint32_t i = 0; i < n_fps && r.ok; i++) {
      uint8_t fl = r.u8();
      if (r.p + fl > r.end) { r.ok = false; break; }
      ctx->sqli.fps.insert(bytes((const char*)r.p, fl));
      r.p += fl;
    }
    // XSS tables: tags, attrs, schemes (compiler/xss.py).
    auto read_names = [&](auto&& sink) {
      uint32_t cnt = r.u32();
      for (uint32_t i = 0; i < cnt && r.ok; i++) {
        uint16_t nl = r.u16();
        if (r.p + nl > r.end) { r.ok = false; break; }
        sink(bytes((const char*)r.p, nl));
        r.p += nl;
      }
    };
    read_names([&](bytes b) { ctx->xss.tags.insert(std::move(b)); });
    read_names([&](bytes b) { ctx->xss.attrs.insert(std::move(b)); });
    read_names([&](bytes b) { ctx->xss.schemes.push_back(std::move(b)); });
  }

  if (!r.ok) return nullptr;
  return ctx.release();
}

// Differential-test exports: run the native detectors standalone.
int cko_sqli(void* h, const uint8_t* s, size_t n) {
  Ctx* ctx = (Ctx*)h;
  return sq_is_sqli(ctx->sqli, bytes((const char*)s, n)) ? 1 : 0;
}

int cko_xss(void* h, const uint8_t* s, size_t n) {
  Ctx* ctx = (Ctx*)h;
  return xs_is_xss(ctx->xss, bytes((const char*)s, n)) ? 1 : 0;
}

void cko_ctx_free(void* h) { delete (Ctx*)h; }

// ---------------------------------------------------------------------------
// Bulk JSON ingest: {"requests":[{method,uri,version,headers,body,
// remote_addr}, ...]} -> the binary request blob cko_tensorize consumes.
// The serving sidecar's hot path hands the raw HTTP body here so Python
// never materializes per-request objects (sidecar/server.py bulk mode).
// ---------------------------------------------------------------------------

namespace bulkjson {

struct P {
  const char* p;
  const char* end;
  bool ok = true;

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      p++;
  }
  bool lit(const char* s) {
    size_t l = strlen(s);
    if ((size_t)(end - p) < l || memcmp(p, s, l) != 0) return false;
    p += l;
    return true;
  }
  // JSON string -> utf-8 bytes (mirrors python str -> encode('utf-8')).
  bool str(bytes& out) {
    ws();
    if (p >= end || *p != '"') return false;
    p++;
    out.clear();
    while (p < end && *p != '"') {
      char c = *p++;
      if (c == '\\' && p < end) {
        char e = *p++;
        switch (e) {
          case 'n': out.push_back('\n'); break;
          case 't': out.push_back('\t'); break;
          case 'r': out.push_back('\r'); break;
          case 'b': out.push_back('\b'); break;
          case 'f': out.push_back('\f'); break;
          case '/': out.push_back('/'); break;
          case '\\': out.push_back('\\'); break;
          case '"': out.push_back('"'); break;
          case 'u': {
            if (end - p < 4) return false;
            unsigned cp = 0;
            for (int k = 0; k < 4; k++) {
              char h = *p++;
              cp <<= 4;
              if (h >= '0' && h <= '9') cp |= h - '0';
              else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
              else return false;
            }
            if (cp >= 0xD800 && cp < 0xDC00 && end - p >= 6 && p[0] == '\\' &&
                p[1] == 'u') {  // surrogate pair
              unsigned lo = 0;
              const char* q = p + 2;
              bool okp = true;
              for (int k = 0; k < 4; k++) {
                char h = *q++;
                lo <<= 4;
                if (h >= '0' && h <= '9') lo |= h - '0';
                else if (h >= 'a' && h <= 'f') lo |= h - 'a' + 10;
                else if (h >= 'A' && h <= 'F') lo |= h - 'A' + 10;
                else { okp = false; break; }
              }
              if (okp && lo >= 0xDC00 && lo < 0xE000) {
                cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
                p = q;
              }
            }
            if (cp < 0x80) out.push_back((char)cp);
            else if (cp < 0x800) {
              out.push_back((char)(0xC0 | (cp >> 6)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            } else if (cp < 0x10000) {
              out.push_back((char)(0xE0 | (cp >> 12)));
              out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            } else {
              out.push_back((char)(0xF0 | (cp >> 18)));
              out.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
              out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            }
            break;
          }
          default: return false;
        }
      } else {
        out.push_back(c);
      }
    }
    if (p >= end) return false;
    p++;  // closing quote
    return true;
  }
  // Skip any JSON value (for unknown fields).
  bool skip() {
    ws();
    if (p >= end) return false;
    char c = *p;
    if (c == '"') { bytes t; return str(t); }
    if (c == '{' || c == '[') {
      char open = c, close = c == '{' ? '}' : ']';
      int depth = 0;
      bool instr = false;
      while (p < end) {
        char d = *p;
        if (instr) {
          if (d == '\\') { p += 2; continue; }
          if (d == '"') instr = false;
        } else {
          if (d == '"') instr = true;
          else if (d == open) depth++;
          else if (d == close) {
            depth--;
            if (depth == 0) { p++; return true; }
          }
        }
        p++;
      }
      return false;
    }
    // Primitive token: must be a valid JSON literal or number — the
    // Python path (json.loads) rejects bare garbage with a 400, and the
    // fast path must not be a second, looser grammar (ADVICE r3).
    const char* s0 = p;
    while (p < end && *p != ',' && *p != '}' && *p != ']' && *p != ' ' &&
           *p != '\t' && *p != '\n' && *p != '\r')
      p++;
    size_t n = (size_t)(p - s0);
    auto is_tok = [&](const char* lit_) {
      return n == strlen(lit_) && memcmp(s0, lit_, n) == 0;
    };
    if (is_tok("true") || is_tok("false") || is_tok("null")) return true;
    // number: -?(0|[1-9][0-9]*)(\.[0-9]+)?([eE][+-]?[0-9]+)?
    const char* q = s0;
    const char* qe = s0 + n;
    if (q < qe && *q == '-') q++;
    if (q >= qe) return false;
    if (*q == '0') q++;
    else if (*q >= '1' && *q <= '9') { while (q < qe && isdigit((unsigned char)*q)) q++; }
    else return false;
    if (q < qe && *q == '.') {
      q++;
      if (q >= qe || !isdigit((unsigned char)*q)) return false;
      while (q < qe && isdigit((unsigned char)*q)) q++;
    }
    if (q < qe && (*q == 'e' || *q == 'E')) {
      q++;
      if (q < qe && (*q == '+' || *q == '-')) q++;
      if (q >= qe || !isdigit((unsigned char)*q)) return false;
      while (q < qe && isdigit((unsigned char)*q)) q++;
    }
    return q == qe;
  }
};

struct Blob {
  bytes data;
  int n_req = 0;

  void str(const bytes& s) {
    uint32_t l = (uint32_t)s.size();
    data.append((const char*)&l, 4);
    data += s;
  }
  void u32(uint32_t v) { data.append((const char*)&v, 4); }
};

}  // namespace bulkjson

extern "C" {

// Parse a bulk-evaluate JSON body into a request blob. Returns a handle
// or nullptr on malformed input; read with cko_blob_{data,len,nreq}.
void* cko_json_to_blob(const uint8_t* json, size_t len) {
  using namespace bulkjson;
  P j{(const char*)json, (const char*)json + len};
  auto out = std::make_unique<Blob>();
  j.ws();
  if (!j.lit("{")) return nullptr;
  bool found = false;
  bool closed = false;
  while (j.p < j.end) {
    j.ws();
    if (j.lit("}")) { closed = true; break; }
    bytes key;
    if (!j.str(key)) return nullptr;
    j.ws();
    if (!j.lit(":")) return nullptr;
    if (key != "requests") {
      if (!j.skip()) return nullptr;
    } else {
      found = true;
      j.ws();
      if (!j.lit("[")) return nullptr;
      j.ws();
      if (!j.lit("]")) {
        while (true) {
          j.ws();
          if (!j.lit("{")) return nullptr;
          bytes method = "GET", uri = "/", version = "HTTP/1.1", body, remote;
          std::vector<std::pair<bytes, bytes>> headers;
          while (true) {
            j.ws();
            if (j.lit("}")) break;
            bytes k;
            if (!j.str(k)) return nullptr;
            j.ws();
            if (!j.lit(":")) return nullptr;
            j.ws();
            if (k == "method") { if (!j.str(method)) return nullptr; }
            else if (k == "uri") { if (!j.str(uri)) return nullptr; }
            else if (k == "version") { if (!j.str(version)) return nullptr; }
            else if (k == "body") { if (!j.str(body)) return nullptr; }
            else if (k == "remote_addr") { if (!j.str(remote)) return nullptr; }
            else if (k == "headers") {
              if (j.lit("[")) {  // [[k, v], ...]
                j.ws();
                if (!j.lit("]")) {
                  while (true) {
                    j.ws();
                    if (!j.lit("[")) return nullptr;
                    bytes hk, hv;
                    if (!j.str(hk)) return nullptr;
                    j.ws();
                    if (!j.lit(",")) return nullptr;
                    if (!j.str(hv)) return nullptr;
                    j.ws();
                    if (!j.lit("]")) return nullptr;
                    headers.emplace_back(hk, hv);
                    j.ws();
                    if (j.lit(",")) continue;
                    if (j.lit("]")) break;
                    return nullptr;
                  }
                }
              } else if (j.lit("{")) {  // {k: v, ...}
                j.ws();
                if (!j.lit("}")) {
                  while (true) {
                    bytes hk, hv;
                    if (!j.str(hk)) return nullptr;
                    j.ws();
                    if (!j.lit(":")) return nullptr;
                    if (!j.str(hv)) return nullptr;
                    headers.emplace_back(hk, hv);
                    j.ws();
                    if (j.lit(",")) continue;
                    if (j.lit("}")) break;
                    return nullptr;
                  }
                }
              } else {
                return nullptr;
              }
            } else {
              if (!j.skip()) return nullptr;  // tenant, unknown fields
            }
            // Strict member separator (ADVICE r3): comma or closing
            // brace only; trailing commas rejected like json.loads.
            j.ws();
            if (j.lit(",")) {
              j.ws();
              if (j.p < j.end && *j.p == '}') return nullptr;
            } else {
              j.ws();
              if (j.p >= j.end || *j.p != '}') return nullptr;
            }
          }
          out->str(method);
          out->str(uri);
          out->str(version);
          out->u32((uint32_t)headers.size());
          for (auto& kv : headers) {
            out->str(kv.first);
            out->str(kv.second);
          }
          out->str(body);
          out->str(remote);
          out->n_req++;
          j.ws();
          if (j.lit(",")) continue;
          if (j.lit("]")) break;
          return nullptr;
        }
      }
    }
    j.ws();
    if (j.lit(",")) {
      j.ws();
      if (j.p < j.end && *j.p == '}') return nullptr;  // trailing comma
    } else {
      j.ws();
      if (j.p >= j.end || *j.p != '}') return nullptr;
    }
  }
  // Strict close + no trailing garbage: the whole body must be exactly
  // one JSON object, as the Python path enforces.
  if (!found || !closed) return nullptr;
  j.ws();
  if (j.p != j.end) return nullptr;
  return out.release();
}

// Scan a request blob for bodies exceeding `limit` bytes
// (SecRequestBodyLimitAction Reject on the bulk fast path: the Python
// side replaces these verdicts with a 413 interruption). Writes up to
// max_out request indexes; returns the number found.
int cko_blob_overlimit(const uint8_t* blob, size_t len, uint32_t limit,
                       int32_t* out_idx, int max_out) {
  size_t pos = 0;
  int idx = 0;
  int found = 0;
  auto rd_len = [&](uint32_t& l) {
    if (pos + 4 > len) return false;
    memcpy(&l, blob + pos, 4);
    pos += 4;
    if (pos + l > len) return false;
    pos += l;
    return true;
  };
  while (pos < len) {
    uint32_t l;
    for (int i = 0; i < 3; i++)
      if (!rd_len(l)) return found;  // method, uri, version
    uint32_t nh;
    if (pos + 4 > len) return found;
    memcpy(&nh, blob + pos, 4);
    pos += 4;
    for (uint32_t h = 0; h < 2 * nh; h++)
      if (!rd_len(l)) return found;
    if (!rd_len(l)) return found;  // body
    if (l > limit && found < max_out) out_idx[found] = idx;
    if (l > limit) found++;
    if (!rd_len(l)) return found;  // remote
    idx++;
  }
  return found;
}

const uint8_t* cko_blob_data(void* h) {
  return (const uint8_t*)((bulkjson::Blob*)h)->data.data();
}
size_t cko_blob_len(void* h) { return ((bulkjson::Blob*)h)->data.size(); }
int cko_blob_nreq(void* h) { return ((bulkjson::Blob*)h)->n_req; }
void cko_blob_free(void* h) { delete (bulkjson::Blob*)h; }

}  // extern "C"

void* cko_tensorize(void* h, const uint8_t* blob, size_t len, int n_req) {
  Ctx* ctx = (Ctx*)h;
  Reader r{blob, blob + len};
  auto res = std::make_unique<Result>();
  size_t n_pipes = ctx->pipelines.size();

  for (int req = 0; req < n_req && r.ok; req++) {
    bytes method = r.str();
    bytes uri = r.str();
    bytes version = r.str();
    uint32_t n_headers = r.u32();
    // A lying header count would demand the allocation below before any
    // per-header read could fail; every header needs >= 8 blob bytes
    // (two length prefixes), so counts past that are corrupt framing.
    if (!r.ok || (size_t)n_headers > (size_t)(r.end - r.p) / 8) {
      r.ok = false;
      break;
    }
    std::vector<std::pair<bytes, bytes>> headers(n_headers);
    for (uint32_t hi = 0; hi < n_headers && r.ok; hi++) {
      headers[hi].first = r.str();
      headers[hi].second = r.str();
    }
    bytes body_full = r.str();
    bytes remote = r.str();
    if (!r.ok) break;

    bytes body = body_full.substr(0, ctx->body_limit);
    int reqbody_error = 0;

    // query / body args
    size_t qpos = uri.find('?');
    bytes path = uri.substr(0, qpos == bytes::npos ? uri.size() : qpos);
    bytes query = qpos == bytes::npos ? bytes() : uri.substr(qpos + 1);

    auto parse_pairs = [](const bytes& raw,
                          std::vector<std::pair<bytes, bytes>>& out) {
      size_t i = 0;
      while (i <= raw.size()) {
        size_t j = raw.find('&', i);
        if (j == bytes::npos) j = raw.size();
        if (j > i) {
          bytes item = raw.substr(i, j - i);
          size_t eq = item.find('=');
          bytes k = eq == bytes::npos ? item : item.substr(0, eq);
          bytes v = eq == bytes::npos ? bytes() : item.substr(eq + 1);
          out.emplace_back(t_urldecode(k), t_urldecode(v));
        }
        if (j == raw.size()) break;
        i = j + 1;
      }
    };

    std::vector<std::pair<bytes, bytes>> args_get, args_post;
    parse_pairs(query, args_get);

    bytes ctype;
    for (auto& kv : headers) {
      if (lower(kv.first) == "content-type") {
        ctype = lower(kv.second);
        break;
      }
    }
    bytes processor;
    MultipartOut mp;
    bytes ctype_raw;
    for (auto& kv : headers) {
      if (lower(kv.first) == "content-type") { ctype_raw = kv.second; break; }
    }
    if (ctx->body_access && !body.empty()) {
      if (ctype.find("json") != bytes::npos) {
        processor = "JSON";
        bytes text = utf8_replace(body);
        std::vector<std::pair<bytes, bytes>> flat;
        JsonParser jp(text, &flat);
        if (jp.parse_document()) {
          args_post = std::move(flat);
        } else {
          reqbody_error = 1;
        }
      } else if (ctype.find("multipart/form-data") != bytes::npos) {
        processor = "MULTIPART";
        mp = parse_multipart(ctype_raw, body);
        args_post = mp.args;
        if (mp.strict_error) reqbody_error = 1;
      } else if (ctype.find("x-www-form-urlencoded") != bytes::npos ||
                 ctype.empty()) {
        processor = "URLENCODED";
        parse_pairs(body, args_post);
      }
    }
    if (!body_full.empty()) {
      res->bodies[processor == "JSON" ? 0 : processor == "URLENCODED" ? 1
                  : processor == "MULTIPART" ? 2 : 3]++;
      res->bodies[4] += (long long)body_full.size();
      res->bodies[5] += reqbody_error;
    }

    // targets, in the exact order of engine/request.py extract()
    std::vector<Target> targets;
    auto add = [&](uint8_t coll, const bytes& name, const bytes& value) {
      targets.push_back({coll, 0, name, value});
    };
    for (auto& kv : args_get) {
      add(C_ARGS, kv.first, kv.second);
      add(C_ARGS_GET, kv.first, kv.second);
      add(C_ARGS_NAMES, kv.first, kv.first);
      add(C_ARGS_GET_NAMES, kv.first, kv.first);
    }
    for (auto& kv : args_post) {
      add(C_ARGS, kv.first, kv.second);
      add(C_ARGS_POST, kv.first, kv.second);
      add(C_ARGS_NAMES, kv.first, kv.first);
      add(C_ARGS_POST_NAMES, kv.first, kv.first);
    }
    for (auto& f : mp.files) {
      add(C_FILES, f.first, f.second);
      add(C_FILES_NAMES, f.first, f.first);
    }
    for (auto& kv : headers) {
      add(C_REQUEST_HEADERS, kv.first, kv.second);
      add(C_REQUEST_HEADERS_NAMES, kv.first, kv.first);
    }
    // first cookie header only (request.header semantics)
    bytes cookie;
    bool has_cookie = false;
    for (auto& kv : headers) {
      if (lower(kv.first) == "cookie") {
        cookie = kv.second;
        has_cookie = true;
        break;
      }
    }
    if (has_cookie && !cookie.empty()) {
      size_t i = 0;
      while (i <= cookie.size()) {
        size_t j = cookie.find(';', i);
        if (j == bytes::npos) j = cookie.size();
        bytes part = cookie.substr(i, j - i);
        size_t a = 0, b = part.size();
        while (a < b && is_ws((uint8_t)part[a])) a++;
        while (b > a && is_ws((uint8_t)part[b - 1])) b--;
        part = part.substr(a, b - a);
        size_t eq = part.find('=');
        bytes name = eq == bytes::npos ? part : part.substr(0, eq);
        bytes value = eq == bytes::npos ? bytes() : part.substr(eq + 1);
        add(C_REQUEST_COOKIES, name, value);
        add(C_REQUEST_COOKIES_NAMES, name, name);
        if (j == cookie.size()) break;
        i = j + 1;
      }
    }

    // scalars (order = the Python dict; only emitted when the kind exists)
    bytes basename = path;
    size_t slash = path.rfind('/');
    if (slash != bytes::npos) basename = path.substr(slash + 1);
    bytes request_line = method + " " + uri + " " + version;
    bytes full_request = request_line + "\r\n";
    for (auto& kv : headers) full_request += kv.first + ": " + kv.second + "\r\n";
    full_request += "\r\n";
    full_request += body;

    bytes scalar_vals[S_COUNT_];
    scalar_vals[S_REQUEST_URI] = uri;
    scalar_vals[S_REQUEST_URI_RAW] = uri;
    scalar_vals[S_REQUEST_FILENAME] = path;
    scalar_vals[S_REQUEST_BASENAME] = basename;
    scalar_vals[S_REQUEST_LINE] = request_line;
    scalar_vals[S_REQUEST_METHOD] = method;
    scalar_vals[S_REQUEST_PROTOCOL] = version;
    scalar_vals[S_QUERY_STRING] = query;
    scalar_vals[S_REQUEST_BODY] = ctx->body_access ? body : bytes();
    scalar_vals[S_FULL_REQUEST] = full_request;
    scalar_vals[S_PATH_INFO] = bytes();
    scalar_vals[S_REMOTE_ADDR] = remote;
    for (auto& kv : headers) {
      if (lower(kv.first) == "host") {
        scalar_vals[S_SERVER_NAME] = kv.second;
        break;
      }
    }
    scalar_vals[S_STATUS_LINE] = bytes();
    scalar_vals[S_RESPONSE_BODY] = bytes();
    scalar_vals[S_AUTH_TYPE] = bytes();
    scalar_vals[S_REQBODY_PROCESSOR] = processor;
    for (int sid = 0; sid < S_COUNT_; sid++) {
      if (ctx->scalar_kind[sid])
        targets.push_back({0xFF, (uint8_t)sid, bytes(), scalar_vals[sid]});
    }

    long long args_combined = 0;
    for (auto& kv : args_get) args_combined += kv.first.size() + kv.second.size();
    for (auto& kv : args_post) args_combined += kv.first.size() + kv.second.size();
    long long numeric_vals[N_COUNT_] = {0};
    numeric_vals[N_REQUEST_BODY_LENGTH] = (long long)body.size();
    numeric_vals[N_REQBODY_ERROR] = reqbody_error;
    numeric_vals[N_MULTIPART_STRICT_ERROR] = mp.strict_error;
    numeric_vals[N_MULTIPART_UNMATCHED_BOUNDARY] = mp.unmatched;
    numeric_vals[N_ARGS_COMBINED_SIZE] = args_combined;
    numeric_vals[N_FULL_REQUEST_LENGTH] = (long long)full_request.size();
    numeric_vals[N_FILES_COMBINED_SIZE] = mp.files_size;
    for (int nid = 0; nid < N_COUNT_; nid++) {
      if (ctx->numeric_kind[nid])
        targets.push_back(
            {0xFE, (uint8_t)nid, bytes(), std::to_string(numeric_vals[nid])});
    }

    // numvars
    std::vector<int32_t> nv(ctx->numvars.size(), 0);
    for (size_t vi = 0; vi < ctx->numvars.size(); vi++) {
      const NumVarSpec& spec = ctx->numvars[vi];
      if (spec.type == 0) {
        nv[vi] = spec.scalar_id < N_COUNT_
                     ? (int32_t)numeric_vals[spec.scalar_id]
                     : 0;  // unknown scalar evaluates to 0 (python parity)
      } else if (spec.type == 1) {
        int32_t count = 0;
        for (auto& t : targets) {
          if (t.coll != spec.coll) continue;
          if (!spec.has_sel || lower(t.name) == spec.sel) count++;
        }
        nv[vi] = count;
      }
      // type 2 (host ops) filled in the kind-resolution loop below.
    }
    res->numvals.push_back(std::move(nv));
    std::vector<int32_t>& nv_ref = res->numvals.back();

    // kind resolution + row packing (waf.py:_tensorize)
    size_t body_cap = std::max<size_t>(32, ctx->body_limit);
    for (auto& t : targets) {
      int32_t kinds[16];
      int nk = 0;
      if (t.coll == 0xFF) {
        kinds[nk++] = (int32_t)ctx->scalar_kind[t.scalar_id];
      } else if (t.coll == 0xFE) {
        kinds[nk++] = (int32_t)ctx->numeric_kind[t.scalar_id];
      } else {
        auto it = ctx->kinds.find(KindKey{t.coll, bytes()});
        if (it != ctx->kinds.end() && it->second) kinds[nk++] = (int32_t)it->second;
        if (!t.name.empty()) {
          auto it2 = ctx->kinds.find(KindKey{t.coll, lower(t.name)});
          if (it2 != ctx->kinds.end() && it2->second && nk < 16)
            kinds[nk++] = (int32_t)it2->second;
          for (auto* rk : ctx->regex_by_coll[t.coll]) {
            if (nk >= 16) break;
            if (rk->dfa.search(t.name)) kinds[nk++] = (int32_t)rk->kind;
          }
        }
      }
      if (nk == 0) continue;
      bytes value = t.value.substr(0, body_cap);

      // Host-evaluated operators (engine/request.py:_eval_hostop): a
      // target whose kind set meets include (and misses exclude) runs
      // the op's transform chain + detector; any hit latches the bit.
      if (ctx->has_hostops) {
        for (size_t vi = 0; vi < ctx->numvars.size(); vi++) {
          const NumVarSpec& spec = ctx->numvars[vi];
          if (spec.type != 2 || nv_ref[vi]) continue;
          bool inc = false, exc = false;
          for (int k = 0; k < nk; k++) {
            int32_t kid = kinds[k];
            if (kid <= 0) continue;
            if ((size_t)kid < spec.inc_kinds.size() && spec.inc_kinds[kid])
              inc = true;
            if ((size_t)kid < spec.exc_kinds.size() && spec.exc_kinds[kid])
              exc = true;
          }
          if (!inc || exc) continue;
          bytes v = t.value;  // full value (python applies pipeline pre-cap)
          for (uint8_t op : spec.pipe_ops) v = apply_op(op, v);
          if (spec.op_id == 0 && sq_is_sqli(ctx->sqli, v)) nv_ref[vi] = 1;
          if (spec.op_id == 1 && xs_is_xss(ctx->xss, v)) nv_ref[vi] = 1;
        }
      }

      for (int off = 0; off < nk; off += 3) {
        Row row;
        row.req = req;
        row.value = value;
        for (int k = 0; k < 3; k++)
          row.kinds[k] = off + k < nk ? kinds[off + k] : 0;
        // host pipeline variants
        row.variants.resize(n_pipes);
        for (size_t pi = 0; pi < n_pipes; pi++) {
          const Pipeline& p = ctx->pipelines[pi];
          bool member = false;
          for (int k = 0; k < 3 && !member; k++) {
            int32_t kid = row.kinds[k];
            if (kid > 0 && (size_t)kid < p.kind_member.size() &&
                p.kind_member[kid])
              member = true;
          }
          if (!member) continue;
          bytes v = value;
          for (uint8_t op : p.ops) v = apply_op(op, v);
          row.variants[pi] = v.substr(0, body_cap);
          res->max_len = std::max(res->max_len, row.variants[pi].size());
        }
        res->max_len = std::max(res->max_len, row.value.size());
        res->rows.push_back(std::move(row));
      }
    }
  }
  if (!r.ok) return nullptr;
  return res.release();
}

int cko_result_rows(void* h) { return (int)((Result*)h)->rows.size(); }
int cko_result_maxlen(void* h) { return (int)((Result*)h)->max_len; }

// Fill caller-allocated buffers. T (rows bucket), L (length bucket), H
// (host pipelines), B (request bucket), NV (numvar count) are the numpy
// array dims; padding rows get req_id = n_req_pad.
int cko_result_export(void* h, uint8_t* data, int32_t* lengths, int32_t* k1,
                      int32_t* k2, int32_t* k3, int32_t* req_id,
                      uint8_t* vdata, int32_t* vlengths, int32_t* numvals,
                      int T, int L, int H, int B, int NV, int n_req_pad) {
  Result* res = (Result*)h;
  if ((int)res->rows.size() > T) return -1;
  memset(data, 0, (size_t)T * L);
  memset(lengths, 0, sizeof(int32_t) * T);
  memset(k1, 0, sizeof(int32_t) * T);
  memset(k2, 0, sizeof(int32_t) * T);
  memset(k3, 0, sizeof(int32_t) * T);
  for (int i = 0; i < T; i++) req_id[i] = n_req_pad;
  if (H > 0) {
    memset(vdata, 0, (size_t)H * T * L);
    memset(vlengths, 0, sizeof(int32_t) * H * T);
  }
  memset(numvals, 0, sizeof(int32_t) * B * NV);

  for (size_t i = 0; i < res->rows.size(); i++) {
    const Row& row = res->rows[i];
    if ((int)row.value.size() > L) return -2;
    memcpy(data + i * L, row.value.data(), row.value.size());
    lengths[i] = (int32_t)row.value.size();
    k1[i] = row.kinds[0];
    k2[i] = row.kinds[1];
    k3[i] = row.kinds[2];
    req_id[i] = row.req;
    for (int pi = 0; pi < H && pi < (int)row.variants.size(); pi++) {
      const bytes& v = row.variants[pi];
      if ((int)v.size() > L) return -2;
      memcpy(vdata + ((size_t)pi * T + i) * L, v.data(), v.size());
      vlengths[(size_t)pi * T + i] = (int32_t)v.size();
    }
  }
  for (size_t req = 0; req < res->numvals.size() && (int)req < B; req++) {
    const auto& nv = res->numvals[req];
    for (size_t vi = 0; vi < nv.size() && (int)vi < NV; vi++)
      numvals[req * NV + vi] = nv[vi];
  }
  return 0;
}

// json, urlencoded, multipart, other, body bytes, parse errors (6 slots).
int cko_result_bodies(void* h, long long* out) {
  if (!h || !out) return -1;
  memcpy(out, ((Result*)h)->bodies, sizeof(((Result*)h)->bodies));
  return 0;
}

void cko_result_free(void* h) { delete (Result*)h; }

}  // extern "C"

// ---------------------------------------------------------------------------
// Window plan: raw request blob -> tier-bucketed, value-dedup'd,
// dispatch-ready layout in ONE GIL-released call (cko_plan_new), then one
// export call (cko_plan_export) scattering rows straight into caller-owned
// staging buffers. Bit-for-bit parity with engine/waf.py::tier_tensors is
// the contract: tier assignment (length bounds + forward/backward merges),
// kind partitioning (mask grouping, stable size sort, small-part merging),
// and the value dedup's sorted-unique key order (np.unique on a void view ==
// unsigned-byte memcmp with first-occurrence representatives) are all
// replicated here and held equal by tests/test_native_tiered.py and
// hack/native_parity_smoke.py. Python keeps only the value-cache probe
// between the two calls.

namespace {

struct PlanTier {
  int length = 0;  // bucketed buffer width (matcher executable width)
  bool has_mask = false;
  long long mask = 0;                // kind-partition block mask
  std::vector<int32_t> sel;          // pair rows: indexes into plan rows
  std::vector<int32_t> first;        // sorted-unique key -> first sel-position
  std::vector<int32_t> inverse;      // pair row -> sorted-unique position
  std::vector<uint8_t> keys;         // sorted-unique key bytes [n_uniq*key_len]
  int key_len = 0;                   // (length + 4) * (1 + H)
};

struct Plan {
  Result* res = nullptr;  // owned
  int n_req_b = 0;        // bucketed request count (pad req_id value)
  int h = 1;              // variant planes = max(1, host pipelines)
  bool empty = false;     // zero extracted rows: one synthetic padding row
  Row synth;
  std::vector<PlanTier> tiers;
  const Row& row(int32_t i) const { return empty ? synth : res->rows[i]; }
  ~Plan() { delete res; }
};

static long long plan_bucket(long long n) {
  long long s = 1;
  while (s < n) s *= 2;
  return s;
}

// One tier's dedup: build per-pair-row keys (value + int32 length + per-plane
// variant + int32 length, each value zero-padded to the tier width — exactly
// the byte image tier_tensors' np.concatenate produces), sort-unique them
// with memcmp order and first-occurrence-by-position representatives.
static void plan_emit(Plan* plan, const std::vector<int32_t>& selv, int length,
                      bool has_mask, long long mask) {
  PlanTier t;
  t.length = length;
  t.has_mask = has_mask;
  t.mask = mask;
  t.sel = selv;
  const int h = plan->h;
  const int np_ = (int)selv.size();
  const int kl = (length + 4) * (1 + h);
  t.key_len = kl;
  std::vector<uint8_t> keys((size_t)np_ * kl, 0);
  for (int r = 0; r < np_; r++) {
    uint8_t* k = keys.data() + (size_t)r * kl;
    const Row& rw = plan->row(selv[r]);
    memcpy(k, rw.value.data(), rw.value.size());
    size_t off = (size_t)length;
    int32_t lg = (int32_t)rw.value.size();
    memcpy(k + off, &lg, 4);
    off += 4;
    for (int hi = 0; hi < h; hi++) {
      const bytes* v =
          hi < (int)rw.variants.size() ? &rw.variants[hi] : nullptr;
      if (v && !v->empty()) memcpy(k + off, v->data(), v->size());
      off += (size_t)length;
      int32_t vl = v ? (int32_t)v->size() : 0;
      memcpy(k + off, &vl, 4);
      off += 4;
    }
  }
  std::vector<int32_t> order(np_);
  for (int r = 0; r < np_; r++) order[r] = r;
  std::sort(order.begin(), order.end(), [&](int32_t a, int32_t b) {
    int c = memcmp(keys.data() + (size_t)a * kl, keys.data() + (size_t)b * kl,
                   (size_t)kl);
    if (c) return c < 0;
    return a < b;  // ties ascending: first occurrence leads its run
  });
  t.inverse.resize(np_);
  const uint8_t* prev = nullptr;
  int uid = -1;
  for (int oi = 0; oi < np_; oi++) {
    const uint8_t* kk = keys.data() + (size_t)order[oi] * kl;
    if (prev == nullptr || memcmp(prev, kk, (size_t)kl) != 0) {
      uid++;
      t.first.push_back(order[oi]);
      t.keys.insert(t.keys.end(), kk, kk + kl);
      prev = kk;
    }
    t.inverse[order[oi]] = uid;
  }
  plan->tiers.push_back(std::move(t));
}

}  // namespace

extern "C" {

void* cko_plan_new(void* h, const uint8_t* blob, size_t len, int n_req,
                   const long long* bounds_in, int n_bounds, int min_tier_rows,
                   const long long* kind_lut, int lut_len, int max_parts,
                   int min_part_rows, int min_len) {
  Ctx* ctx = (Ctx*)h;
  Result* res = (Result*)cko_tensorize(h, blob, len, n_req);
  if (!res) return nullptr;
  auto plan = std::make_unique<Plan>();
  plan->res = res;
  plan->n_req_b = (int)plan_bucket(std::max(1, n_req));
  plan->h = std::max<int>(1, (int)ctx->pipelines.size());

  const int n_rows = (int)res->rows.size();
  std::vector<int32_t> real;
  if (n_rows == 0) {
    // tier_tensors keeps one padding row when no real rows exist: all-zero
    // value, kinds 0, req_id = the pad bucket.
    plan->empty = true;
    plan->synth.req = plan->n_req_b;
    plan->synth.kinds[0] = plan->synth.kinds[1] = plan->synth.kinds[2] = 0;
    real.push_back(0);
  } else {
    real.resize(n_rows);
    for (int i = 0; i < n_rows; i++) real[i] = i;
  }

  const int cap = (int)plan_bucket(
      std::max<long long>(min_len, (long long)res->max_len));
  std::vector<int> bounds;
  for (int i = 0; i < n_bounds; i++)
    if (bounds_in[i] < cap) bounds.push_back((int)bounds_in[i]);
  bounds.push_back(cap);

  std::vector<int> row_max(real.size());
  for (size_t i = 0; i < real.size(); i++) {
    const Row& rw = plan->row(real[i]);
    size_t rm = rw.value.size();
    for (const bytes& v : rw.variants) rm = std::max(rm, v.size());
    row_max[i] = (int)rm;
  }

  // First-fit bound assignment, row order preserved within each tier.
  struct RawTier {
    int b;
    std::vector<int32_t> sel;
  };
  std::vector<RawTier> raw;
  {
    std::vector<int32_t> remaining(real.size());
    for (size_t i = 0; i < real.size(); i++) remaining[i] = (int32_t)i;
    for (int b : bounds) {
      std::vector<int32_t> fit, rest;
      for (int32_t i : remaining)
        (row_max[i] <= b ? fit : rest).push_back(i);
      remaining.swap(rest);
      if (!fit.empty()) {
        RawTier rt;
        rt.b = b;
        rt.sel.reserve(fit.size());
        for (int32_t i : fit) rt.sel.push_back(real[i]);
        raw.push_back(std::move(rt));
      }
    }
  }

  // Forward merge (absorb sub-minimum tiers into the next wider bound),
  // then backward merge of a trailing sub-minimum tier at the wider width.
  std::vector<RawTier> merged;
  for (size_t i = 0; i < raw.size(); i++) {
    RawTier cur = std::move(raw[i]);
    while ((int)cur.sel.size() < min_tier_rows && i + 1 < raw.size()) {
      i++;
      cur.b = raw[i].b;
      cur.sel.insert(cur.sel.end(), raw[i].sel.begin(), raw[i].sel.end());
    }
    merged.push_back(std::move(cur));
  }
  if (merged.size() > 1 &&
      (int)merged.back().sel.size() < min_tier_rows) {
    RawTier last = std::move(merged.back());
    merged.pop_back();
    merged.back().b = std::max(merged.back().b, last.b);
    merged.back().sel.insert(merged.back().sel.end(), last.sel.begin(),
                             last.sel.end());
  }

  for (RawTier& mt : merged) {
    const int length = (int)plan_bucket(std::max(min_len, mt.b));
    if (kind_lut == nullptr || max_parts <= 1) {
      plan_emit(plan.get(), mt.sel, length, false, 0);
      continue;
    }
    // Kind partitioning: rows grouped by the OR of their kinds' class
    // masks; ascending-mask groups, stable sort by descending size, then
    // sub-minimum partitions merge into the largest (union mask).
    auto lut_at = [&](int32_t k) -> long long {
      return (k >= 0 && k < lut_len) ? kind_lut[k] : 0;
    };
    std::map<long long, std::vector<int32_t>> by_mask;
    for (int32_t ri : mt.sel) {
      const Row& rw = plan->row(ri);
      long long pm =
          lut_at(rw.kinds[0]) | lut_at(rw.kinds[1]) | lut_at(rw.kinds[2]);
      by_mask[pm].push_back(ri);
    }
    struct Part {
      std::vector<int32_t> sel;
      long long mask;
    };
    std::vector<Part> parts;
    for (auto& kv : by_mask)
      parts.push_back(Part{std::move(kv.second), kv.first});
    std::stable_sort(parts.begin(), parts.end(),
                     [](const Part& a, const Part& b) {
                       return a.sel.size() > b.sel.size();
                     });
    while (parts.size() > 1 &&
           (int)parts.back().sel.size() < min_part_rows) {
      Part small = std::move(parts.back());
      parts.pop_back();
      parts[0].sel.insert(parts[0].sel.end(), small.sel.begin(),
                          small.sel.end());
      parts[0].mask |= small.mask;
    }
    if (parts.size() == 1) {
      // Single partition: scan-everything trace over the ORIGINAL tier
      // order (a content-dependent mask would mint executables per mix).
      plan_emit(plan.get(), mt.sel, length, false, 0);
    } else {
      for (Part& p : parts) plan_emit(plan.get(), p.sel, length, true, p.mask);
    }
  }
  return plan.release();
}

int cko_plan_ntiers(void* h) { return (int)((Plan*)h)->tiers.size(); }

// Per tier: length, n_pairs, n_unique, key_len, has_mask, mask (6 slots).
int cko_plan_tiers(void* h, long long* out) {
  Plan* plan = (Plan*)h;
  for (size_t i = 0; i < plan->tiers.size(); i++) {
    const PlanTier& t = plan->tiers[i];
    out[i * 6 + 0] = t.length;
    out[i * 6 + 1] = (long long)t.sel.size();
    out[i * 6 + 2] = (long long)t.first.size();
    out[i * 6 + 3] = t.key_len;
    out[i * 6 + 4] = t.has_mask ? 1 : 0;
    out[i * 6 + 5] = t.mask;
  }
  return 0;
}

// Copy one tier's sorted-unique dedup keys (n_unique * key_len bytes) out
// for the Python value-cache probe.
int cko_plan_keys(void* h, int ti, uint8_t* out) {
  Plan* plan = (Plan*)h;
  if (ti < 0 || ti >= (int)plan->tiers.size()) return -1;
  const PlanTier& t = plan->tiers[ti];
  memcpy(out, t.keys.data(), t.keys.size());
  return 0;
}

// Scatter every tier into caller-owned staging buffers in one call.
//
//   ptrs: 9 buffer addresses per tier — data, lengths, k1, k2, k3, req_id,
//         vdata, vlengths, uid (the tier-tuple order _tier_specs consumes).
//   dims: 4 per tier — U (unique-row bucket), P (pair-row bucket), u_pad
//         (found-row uid base = bucketed miss count), n_miss.
//   miss_all/miss_off: per-tier ascending unique indexes that MISSED the
//         value cache (concatenated + offsets). NULL miss_all = no cache:
//         every unique row exports and uid = inverse.
//
// Buffers may be dirty (staging-arena reuse): real rows are written with
// their padding tails memset'd, and only the pad regions beyond them are
// zeroed — never the full buffer. Pad req_id rows get n_req_pad.
int cko_plan_export(void* h, const unsigned long long* ptrs,
                    const long long* dims, const int32_t* miss_all,
                    const long long* miss_off, int32_t* numvals, int B, int NV,
                    int n_req_pad) {
  Plan* plan = (Plan*)h;
  const int H = plan->h;
  for (size_t ti = 0; ti < plan->tiers.size(); ti++) {
    const PlanTier& t = plan->tiers[ti];
    const int L = t.length;
    const int n_u = (int)t.first.size();
    const int n_p = (int)t.sel.size();
    const bool identity = miss_all == nullptr;
    const long long U = dims[ti * 4 + 0];
    const long long P = dims[ti * 4 + 1];
    const long long u_pad = dims[ti * 4 + 2];
    const int n_miss = identity ? n_u : (int)dims[ti * 4 + 3];
    if (n_miss > U || n_p > P) return -1;
    const int32_t* miss = identity ? nullptr : miss_all + miss_off[ti];

    // unique j -> exported uid (miss rows first, found rows above u_pad in
    // ascending-j order — sorted(found.items()) parity).
    std::vector<int32_t> remap;
    if (!identity) {
      remap.assign(n_u, 0);
      std::vector<uint8_t> is_miss(n_u, 0);
      for (int r = 0; r < n_miss; r++) {
        if (miss[r] < 0 || miss[r] >= n_u) return -2;
        remap[miss[r]] = r;
        is_miss[miss[r]] = 1;
      }
      int fr = 0;
      for (int j = 0; j < n_u; j++)
        if (!is_miss[j]) remap[j] = (int32_t)(u_pad + fr++);
    }

    uint8_t* d = (uint8_t*)ptrs[ti * 9 + 0];
    int32_t* lg = (int32_t*)ptrs[ti * 9 + 1];
    int32_t* k1 = (int32_t*)ptrs[ti * 9 + 2];
    int32_t* k2 = (int32_t*)ptrs[ti * 9 + 3];
    int32_t* k3 = (int32_t*)ptrs[ti * 9 + 4];
    int32_t* rid = (int32_t*)ptrs[ti * 9 + 5];
    uint8_t* vd = (uint8_t*)ptrs[ti * 9 + 6];
    int32_t* vl = (int32_t*)ptrs[ti * 9 + 7];
    int32_t* uidp = (int32_t*)ptrs[ti * 9 + 8];

    for (int r = 0; r < n_miss; r++) {
      const int j = identity ? r : miss[r];
      const Row& rw = plan->row(t.sel[t.first[j]]);
      const size_t vlen = rw.value.size();
      memcpy(d + (size_t)r * L, rw.value.data(), vlen);
      memset(d + (size_t)r * L + vlen, 0, (size_t)L - vlen);
      lg[r] = (int32_t)vlen;
      for (int hi = 0; hi < H; hi++) {
        const bytes* v =
            hi < (int)rw.variants.size() ? &rw.variants[hi] : nullptr;
        const size_t hl = v ? v->size() : 0;
        uint8_t* dst = vd + ((size_t)hi * U + r) * L;
        if (hl) memcpy(dst, v->data(), hl);
        memset(dst + hl, 0, (size_t)L - hl);
        vl[(size_t)hi * U + r] = (int32_t)hl;
      }
    }
    if (n_miss < U) {
      memset(d + (size_t)n_miss * L, 0, (size_t)(U - n_miss) * L);
      memset(lg + n_miss, 0, sizeof(int32_t) * (size_t)(U - n_miss));
      for (int hi = 0; hi < H; hi++) {
        memset(vd + ((size_t)hi * U + n_miss) * L, 0,
               (size_t)(U - n_miss) * L);
        memset(vl + (size_t)hi * U + n_miss, 0,
               sizeof(int32_t) * (size_t)(U - n_miss));
      }
    }
    for (int r = 0; r < n_p; r++) {
      const Row& rw = plan->row(t.sel[r]);
      k1[r] = rw.kinds[0];
      k2[r] = rw.kinds[1];
      k3[r] = rw.kinds[2];
      rid[r] = rw.req;
      uidp[r] = identity ? t.inverse[r] : remap[t.inverse[r]];
    }
    if (n_p < P) {
      memset(k1 + n_p, 0, sizeof(int32_t) * (size_t)(P - n_p));
      memset(k2 + n_p, 0, sizeof(int32_t) * (size_t)(P - n_p));
      memset(k3 + n_p, 0, sizeof(int32_t) * (size_t)(P - n_p));
      memset(uidp + n_p, 0, sizeof(int32_t) * (size_t)(P - n_p));
      for (long long r = n_p; r < P; r++) rid[r] = n_req_pad;
    }
  }
  memset(numvals, 0, sizeof(int32_t) * (size_t)B * NV);
  const Result* res = plan->res;
  for (size_t req = 0; req < res->numvals.size() && (int)req < B; req++) {
    const auto& nv = res->numvals[req];
    for (size_t vi = 0; vi < nv.size() && (int)vi < NV; vi++)
      numvals[req * NV + vi] = nv[vi];
  }
  return 0;
}

int cko_plan_bodies(void* h, long long* out) {
  if (!h) return -1;
  return cko_result_bodies(((Plan*)h)->res, out);
}

void cko_plan_free(void* h) { delete (Plan*)h; }

}  // extern "C"

// ---------------------------------------------------------------------------
// Prefilter confirm (engine/waf.py:_confirm_prefilter): the exact DFAs of
// the prefiltered groups, laid out for a raw-byte walk, plus each group's
// transform pipeline. One cko_confirm_run call confirms every device
// prefilter positive of one tier; semantics are DFA.search
// (compiler/re_dfa.py) over apply_pipeline(row) — emit on transition,
// match_end at end of input, always_match — and
// tests/test_prefilter_confirm_native.py holds the two bit-for-bit equal.
// ---------------------------------------------------------------------------

namespace {

constexpr uint32_t kConfirmEmit = 0x80000000u;

struct ConfirmGroup {
  uint32_t pipe = 0;   // index into Confirm::pipes (memo key with the row)
  int32_t slot = -1;   // host variant plane, or -1: transform the raw row
  uint32_t S = 0;
  bool always = false;
  std::vector<uint32_t> table;     // [S*256]: next state | kConfirmEmit
  std::vector<uint8_t> match_end;  // [S]

  bool search(const uint8_t* p, size_t n) const {
    if (always) return true;
    const uint32_t* t = table.data();
    uint32_t s = 0;
    for (size_t i = 0; i < n; i++) {
      const uint32_t v = t[(size_t)s * 256 + p[i]];
      if (v & kConfirmEmit) return true;
      s = v;
    }
    return match_end[s] != 0;
  }
};

struct Confirm {
  std::vector<std::vector<uint8_t>> pipes;  // transform opcodes, in order
  std::vector<ConfirmGroup> groups;
};

}  // namespace

extern "C" {

// Blob (little-endian): u32 n_pipes, per pipe {u32 n_ops, u8 ops[]};
// u32 n_groups, per group {u32 pipe, i32 slot, u32 S, u8 always,
// u32 table[S*256], u8 match_end[S]}. NULL on a malformed blob.
void* cko_confirm_new(const uint8_t* blob, size_t len) {
  Reader r{blob, blob + len};
  auto cf = std::make_unique<Confirm>();
  const uint32_t n_pipes = r.u32();
  for (uint32_t i = 0; i < n_pipes && r.ok; i++) {
    const uint32_t n_ops = r.u32();
    if (!r.ok || (size_t)(r.end - r.p) < n_ops) return nullptr;
    std::vector<uint8_t> ops;
    for (uint32_t j = 0; j < n_ops; j++) {
      const uint8_t op = r.p[j];
      if (op >= OP_COUNT_) return nullptr;
      if (op != OP_NONE) ops.push_back(op);
    }
    r.p += n_ops;
    cf->pipes.push_back(std::move(ops));
  }
  const uint32_t n_groups = r.u32();
  for (uint32_t i = 0; i < n_groups && r.ok; i++) {
    ConfirmGroup g;
    g.pipe = r.u32();
    g.slot = (int32_t)r.u32();
    g.S = r.u32();
    g.always = r.u8() != 0;
    if (!r.ok || g.pipe >= cf->pipes.size() || g.slot < -1 ||
        g.S >= kConfirmEmit || (g.S == 0 && !g.always))
      return nullptr;
    const size_t cells = (size_t)g.S * 256;
    if ((size_t)(r.end - r.p) / 4 < cells) return nullptr;
    g.table.resize(cells);
    if (cells) memcpy(g.table.data(), r.p, cells * 4);
    r.p += cells * 4;
    for (uint32_t v : g.table)
      if ((v & ~kConfirmEmit) >= g.S) return nullptr;
    if ((size_t)(r.end - r.p) < g.S) return nullptr;
    g.match_end.assign(r.p, r.p + g.S);
    r.p += g.S;
    cf->groups.push_back(std::move(g));
  }
  if (!r.ok || r.p != r.end) return nullptr;
  return cf.release();
}

void cko_confirm_free(void* h) { delete (Confirm*)h; }

// Confirm n_pos device prefilter positives of one tier. data [U, L] uint8
// and lengths [U] int32 are the tier's raw rows, vdata [H, U, L] /
// vlengths [H, U] its host-variant planes; pos_row[k] / pos_group[k]
// name positive k (group = index into the handle's groups). A group's
// pipeline runs once per (pipeline, row); out[k] = 1 iff the exact DFA
// matches. Returns 0, or a negative code when an argument is out of
// range — out is then unspecified and the caller confirms the window on
// the Python path.
int cko_confirm_run(void* h, const uint8_t* data, const int32_t* lengths,
                    int U, int L, const uint8_t* vdata,
                    const int32_t* vlengths, int H, const int32_t* pos_row,
                    const int32_t* pos_group, int n_pos, uint8_t* out) {
  const Confirm* cf = (const Confirm*)h;
  if (!cf || U < 0 || L < 0 || H < 0 || n_pos < 0) return -1;
  if (n_pos == 0) return 0;
  if (!data || !lengths || !pos_row || !pos_group || !out) return -1;
  const size_t n_pipes = cf->pipes.size();
  std::vector<int32_t> memo(n_pipes * (size_t)U, -1);
  std::vector<bytes> vals;
  for (int k = 0; k < n_pos; k++) {
    const int32_t row = pos_row[k], gi = pos_group[k];
    if (row < 0 || row >= U) return -2;
    if (gi < 0 || (size_t)gi >= cf->groups.size()) return -3;
    const ConfirmGroup& g = cf->groups[(size_t)gi];
    if (g.slot >= 0) {
      if (g.slot >= H || !vdata || !vlengths) return -4;
      const int32_t vl = vlengths[(size_t)g.slot * (size_t)U + (size_t)row];
      if (vl < 0 || vl > L) return -5;
      out[k] = g.search(
          vdata + ((size_t)g.slot * (size_t)U + (size_t)row) * (size_t)L,
          (size_t)vl);
      continue;
    }
    const int32_t lg = lengths[row];
    if (lg < 0 || lg > L) return -5;
    const uint8_t* raw = data + (size_t)row * (size_t)L;
    const std::vector<uint8_t>& ops = cf->pipes[g.pipe];
    if (ops.empty()) {
      out[k] = g.search(raw, (size_t)lg);
      continue;
    }
    int32_t& mi = memo[(size_t)g.pipe * (size_t)U + (size_t)row];
    if (mi < 0) {
      bytes v((const char*)raw, (size_t)lg);
      for (uint8_t op : ops) v = apply_op(op, v);
      mi = (int32_t)vals.size();
      vals.push_back(std::move(v));
    }
    const bytes& v = vals[(size_t)mi];
    out[k] = g.search((const uint8_t*)v.data(), v.size());
  }
  return 0;
}

}  // extern "C"
