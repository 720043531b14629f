"""The plain reference: what each push must be answered, from the policy
and the bytes alone.

A straightforward implementation of the semantics the sidecar promises
(bit-identical to Cilium's proxylib on the same bytes), written against
the public protocol and policy descriptions and importing nothing of the
program:

- r2d2 (proxylib/r2d2/r2d2parser.go): a frame is one CRLF-terminated
  line "<cmd> [file]"; a request matches a rule when ``cmd`` is equal
  (or unset) and ``file`` is found by a regex search (Go
  ``regexp.MatchString``); a denied frame is dropped and ``ERROR\\r\\n``
  is injected toward the client; replies pass line by line.
- HTTP/1.x (Envoy's cilium.l7policy): a frame is the head through
  CRLFCRLF plus a Content-Length body; ``method`` and ``path`` are
  anchored regexes; a denied frame is dropped and the 403 "Access
  denied" response is injected toward the client; replies pass whole.
- DNS over TCP (RFC 1035 section 4.2.2): a frame is a 2-byte length
  prefix and the message; the first question's name, ASCII letters
  folded, is matched exactly (``matchName``) or by wildcard
  (``matchPattern``: a leading ``*.`` stands for one or more labels);
  a denied frame is dropped with nothing injected; replies pass.

The proxylib OnData loop (proxylib/proxylib/connection.go) asks the
parser again after each PASS or DROP, up to 16 ops, and stops at MORE.
The datapath keeps the bytes a verdict has not consumed and hands the
parser all of them on the next call; a PASS or DROP longer than what is
buffered consumes later input (``Reference.feed``).
"""

from __future__ import annotations

import re

MORE, PASS, DROP = 0, 1, 2  # proxylib op codes
OK = 0
OPS_CAPACITY = 16
INJECT_CAPACITY = 1024
HTTP_403 = (b"HTTP/1.1 403 Forbidden\r\ncontent-type: text/plain\r\n"
            b"content-length: 13\r\n\r\nAccess denied")
HTTP_MAX_HEAD = 1 << 15
DNS_MAX_LABEL = 63
DNS_MAX_LABELS = 40


# --- rules ----------------------------------------------------------------

def _wildcard(pattern: str) -> re.Pattern:
    body, head = pattern.rstrip(".").lower(), ""
    if body.startswith("*."):
        head, body = "([^.]+[.])+", body[2:]
    return re.compile(head + "".join(
        "[^.]*" if ch == "*" else re.escape(ch) for ch in body))


def compile_rules(proto: str, rules: list[dict]):
    """A predicate over a parsed request: does any rule allow it?"""
    if proto == "r2d2":
        rs = [(r.get("cmd", ""), re.compile(r["file"]) if r.get("file")
               else None) for r in rules]
        return lambda cmd, file: any(
            (not c or c == cmd) and (f is None or f.search(file))
            for c, f in rs)
    if proto == "http":
        rs = [(re.compile(r["method"]) if r.get("method") else None,
               re.compile(r["path"]) if r.get("path") else None)
              for r in rules]
        return lambda method, path: any(
            (m is None or m.fullmatch(method))
            and (p is None or p.fullmatch(path)) for m, p in rs)
    if proto == "dns":
        names = {r["matchName"].rstrip(".").lower()
                 for r in rules if "matchName" in r}
        pats = [_wildcard(r["matchPattern"]) for r in rules
                if "matchPattern" in r]
        return lambda name: name is not None and (
            name in names or any(p.fullmatch(name) for p in pats))
    raise ValueError(f"no reference for protocol {proto!r}")


# --- parsers: (op, n) and the inject toward the client --------------------

def r2d2_parse(allow, reply: bool, buf: bytes):
    idx = buf.find(b"\r\n")
    if idx < 0:
        return MORE, 1, b""
    if reply:
        return PASS, idx + 2, b""
    fields = buf[:idx].decode("utf-8", "surrogateescape").split(" ")
    file = fields[1] if len(fields) == 2 else ""
    if allow(fields[0], file):
        return PASS, idx + 2, b""
    return DROP, idx + 2, b"ERROR\r\n"


def http_parse(allow, reply: bool, buf: bytes):
    if reply:
        return (PASS, len(buf), b"") if buf else (MORE, 1, b"")
    end = buf.find(b"\r\n\r\n")
    if end < 0:
        if len(buf) > HTTP_MAX_HEAD:
            return DROP, len(buf), HTTP_403
        return MORE, 1, b""
    head_len, body_len = end + 4, 0
    lower = buf[:head_len].lower()
    at = lower.find(b"\r\ncontent-length:")
    if at >= 0:
        try:
            body_len = max(0, int(lower[at + 17:lower.find(b"\r\n", at + 2)]))
        except ValueError:
            body_len = 0
    if len(buf) < head_len + body_len:
        return MORE, 1, b""
    n = head_len + body_len
    line = buf[:head_len].decode("utf-8", "surrogateescape").split("\r\n")[0]
    parts = line.split(" ")
    if len(parts) >= 3 and allow(parts[0], parts[1]):
        return PASS, n, b""
    return DROP, n, HTTP_403


def dns_name_of(frame: bytes):
    """First question's name (letters folded), or None if malformed."""
    end = len(frame)
    if end < 2 + 12 + 1 + 4 or ((frame[6] << 8) | frame[7]) < 1:
        return None
    pos, labels = 14, []
    while pos < end:
        lb = frame[pos]
        if lb == 0:
            if pos + 5 > end:
                return None
            name = b".".join(labels)
            return bytes(b + 32 if 65 <= b <= 90 else b
                         for b in name).decode("latin-1")
        if lb > DNS_MAX_LABEL or len(labels) >= DNS_MAX_LABELS \
                or pos + 1 + lb > end:
            return None
        labels.append(frame[pos + 1:pos + 1 + lb])
        pos += 1 + lb
    return None


def dns_parse(allow, reply: bool, buf: bytes):
    if len(buf) < 2:
        return MORE, 1, b""
    need = 2 + ((buf[0] << 8) | buf[1])
    if len(buf) < need:
        return MORE, 1, b""
    if reply or allow(dns_name_of(buf[:need])):
        return PASS, need, b""
    return DROP, need, b""


PARSERS = {"r2d2": r2d2_parse, "http": http_parse, "dns": dns_parse}


# --- connections ------------------------------------------------------------

class Reference:
    """Expected answers for the connections of one cell.

    ``feed(i, reply, data)`` returns what push ``data`` on connection
    ``i`` must be answered: ``(result, [(op, n), ...], inject toward
    the server, inject toward the client)``."""

    def __init__(self, policies: list[dict], conn_policy: list[str]):
        by_name = {p["name"]: p for p in policies}
        self.conns = []
        for name in conn_policy:
            pol = by_name[name]
            self.conns.append((PARSERS[pol["proto"]],
                               compile_rules(pol["proto"], pol["rules"])))
        # (conn, reply) -> [retained bytes, PASS owed, DROP owed]
        self.state: dict = {}

    def feed(self, i: int, reply: bool, data: bytes) -> tuple:
        parse, allow = self.conns[i]
        st = self.state.setdefault((i, reply), [b"", 0, 0])
        buf, owe_pass, owe_drop = st
        take = min(owe_pass or owe_drop, len(data))
        if owe_pass:
            owe_pass -= take
        elif owe_drop:
            owe_drop -= take
        buf += data[take:]
        ops, inject, rest = [], b"", buf
        while len(ops) < OPS_CAPACITY:
            op, n, inj = parse(allow, reply, rest)
            ops.append((op, n))
            if inj:
                # The parser injects toward the client: the reply
                # direction's buffer, whichever direction was parsed.
                inject = (inject + inj)[:INJECT_CAPACITY]
            if op == MORE:
                break
            rest = rest[n:]
        for op, n in ops:
            if op in (PASS, DROP):
                used = min(n, len(buf))
                buf = buf[used:]
                if op == PASS:
                    owe_pass += n - used
                else:
                    owe_drop += n - used
        st[:] = [buf, owe_pass, owe_drop]
        return OK, ops, b"", inject


def apply_ops(data: bytes, ops) -> bytes:
    """Bytes a shim forwards for one whole-frame push under ``ops``."""
    out, pos = bytearray(), 0
    for op, n in ops:
        if op == PASS:
            out += data[pos:pos + n]
            pos += n
        elif op == DROP:
            pos += n
    return bytes(out)
