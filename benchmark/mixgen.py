"""The one traffic generator: connections, their frames, and each push.

A traffic file (``benchmark/traffic/<name>.json``) holds only
parameters; this module turns them, the configuration and ``--seed``
into connections and the bytes of every push.  The same seed gives the
same connections, the same frames and the same sequence of pushes on
each connection; another seed draws the same numbers of connections of
each protocol and category, in another order, with other frames.

Parameters of a traffic file:

- ``loop``: ``closed`` (each connection has at most one push unanswered
  and at most ``outstanding`` are unanswered in all) or ``poisson``
  (open loop: pushes at exponential gaps at ``rate`` per second, held at
  the shim for at most ``client_hold_ms`` or ``client_batch`` pushes).
- ``conns``: connections; their protocols split as the configuration's
  ``protocols`` shares say.
- ``frame_shares``: the share of verdicts from each category of
  connection: ``complete`` (one whole frame a push), ``partial`` (half a
  frame a push), ``pipelined`` (two whole frames a push) and ``reply``
  (reply-direction bytes).
- ``on_io_conns``: complete-frame connections driven one call at a time
  through ``ShimConnection.on_io`` (an equal share of each protocol).
- ``warmup_s``: seconds of the same traffic before the window opens.
- ``variants``: request frames drawn per connection.
- ``check_pushes``: how many pushes, at most, the reference checks after
  the window (whole connections, drawn from the seed, every stratum of
  protocol, category and lane represented).

The HTTP and DNS frame generators below are copies of the repository's
served-path smoke test; the r2d2 frames are those of upstream's r2d2
parser tests; the DNS encoder is RFC 1035 section 4.2.2.
"""

from __future__ import annotations

import numpy as np

from . import deploy

CATEGORIES = ("complete", "partial", "pipelined", "reply")
COMPLETE, PARTIAL, PIPELINED, REPLY = range(4)
# Verdicts a push of each category yields, on average.
VERDICTS_PER_PUSH = {"complete": 1.0, "partial": 0.5, "pipelined": 2.0,
                     "reply": 1.0}
PICKS = 1024  # frame picks per connection; push k uses pick k mod PICKS


def encode_dns_query(name: str, qtype: int = 1, qid: int = 0) -> bytes:
    """One length-prefixed DNS-over-TCP query for ``name``."""
    labels = [l for l in name.encode("latin-1", "replace").split(b".") if l]
    qn = b"".join(bytes([len(l)]) + l for l in labels) + b"\x00"
    msg = (qid.to_bytes(2, "big") + b"\x01\x00" + b"\x00\x01" + b"\x00" * 6
           + qn + qtype.to_bytes(2, "big") + b"\x00\x01")
    return len(msg).to_bytes(2, "big") + msg


def _http_frames(rng, p: int, cfg: dict, n: int) -> list[bytes]:
    n_pol = cfg["http_policies"]
    n_lit = cfg["http_literal_rules"]
    out = []
    for _ in range(n):
        roll = rng.random()
        j = int(rng.integers(0, n_lit))
        k = int(rng.integers(0, 1 << 20))
        if roll < 0.40:  # literal tier, allowed
            path = f"/svc{p:03d}/r{j:02d}/o{k}"
        elif roll < 0.55:  # regex tier, allowed
            path = f"/g{j % cfg['http_dfa_rules']:02d}/x{k:x}/item/{k}"
        elif roll < 0.65:  # NFA tier, allowed
            ab = "".join("ab"[b] for b in rng.integers(0, 2, 12))
            path = f"/n{j % cfg['http_nfa_rules']:02d}/{ab}a{ab[:7]}/x"
        elif roll < 0.80:  # another policy's literal: denied
            path = f"/svc{(p + 1) % max(n_pol, 2):03d}/r{j:02d}/o{k}"
        elif roll < 0.90:  # regex near miss (upper case): denied
            path = f"/g00/X{k:X}/item/{k}"
        else:
            path = f"/private/{k}"
        out.append(f"GET {path} HTTP/1.1\r\nHost: svc.local\r\n"
                   f"User-Agent: bench\r\n\r\n".encode())
    return out


def _dns_frames(rng, p: int, cfg: dict, n: int) -> list[bytes]:
    n_pol = cfg["dns_policies"]
    out = []
    for _ in range(n):
        roll = rng.random()
        j = int(rng.integers(0, cfg["dns_exact_rules"]))
        if roll < 0.45:
            name = deploy.dns_name(p, j)
        elif roll < 0.65:
            jp = j % cfg["dns_pattern_rules"]
            name = f"h{int(rng.integers(0, 999))}.w{jp:02d}.svc.local"
        elif roll < 0.85:
            name = deploy.dns_name((p + 1) % max(n_pol, 2), j)
        else:
            name = f"x{int(rng.integers(0, 999))}.example.com"
        out.append(encode_dns_query(name, qid=int(rng.integers(0, 1 << 16))))
    return out


def _r2d2_frames(rng, p: int, cfg: dict, n: int) -> list[bytes]:
    """The frames of proxylib's r2d2 parser tests, in equal shares: READ
    inside and outside ``/public/``, WRITE, HALT and RESET."""
    out = []
    for _ in range(n):
        roll = int(rng.integers(0, 5))
        k = int(rng.integers(0, 997))
        out.append((f"READ /public/f{k}.txt\r\n", f"READ /private/f{k}\r\n",
                    f"WRITE /public/f{k}.txt\r\n", "HALT\r\n",
                    "RESET\r\n")[roll].encode())
    return out


FRAMES = {"http": _http_frames, "dns": _dns_frames, "r2d2": _r2d2_frames}
REPLY = {"http": b"HTTP/1.1 200 OK\r\ncontent-length: 2\r\n\r\nok",
         "r2d2": b"OK\r\n"}


def _split(total: int, shares: dict) -> dict:
    """Whole counts that sum to ``total``, in proportion to ``shares``;
    the first key takes what rounding leaves."""
    keys = list(shares)
    norm = sum(shares.values())
    counts = {k: int(round(total * shares[k] / norm)) for k in keys[1:]}
    counts[keys[0]] = total - sum(counts.values())
    return counts


class Traffic:
    """Connections of one cell and the bytes of every push.

    Connection ``i`` has conn id ``i + 1``.  Arrays are indexed by ``i``.
    """

    def __init__(self, params: dict, cfg: dict, seed: int, width: int):
        self.width = width
        rng = np.random.default_rng(seed)
        n = self.n = int(params["conns"])
        v = self.variants = int(params.get("variants", 8))
        protos = _split(n, cfg["protocols"])
        fs = params["frame_shares"]
        shares = {c: fs.get(c, 0.0) / VERDICTS_PER_PUSH[c]
                  for c in CATEGORIES}
        # Every seed draws the same number of conns of each protocol and
        # category; only their order differs.
        pairs = []
        for pr, m in protos.items():
            per_cat = _split(m, shares)
            pairs += [(pr, j) for j, c in enumerate(CATEGORIES)
                      for _ in range(per_cat[c])]
        pairs = [pairs[j] for j in rng.permutation(n)]
        proto = np.array([p for p, _ in pairs])
        cat = np.array([c for _, c in pairs], np.int64)
        self.category = cat
        self.proto = proto
        self.cid = np.arange(1, n + 1, dtype=np.uint64)
        names = deploy.policy_names(cfg)
        self.policy: list[str] = []
        self.port = np.zeros(n, np.int64)
        corpora = []
        seen = dict.fromkeys(protos, 0)
        for i in range(n):
            pr = str(proto[i])
            p = seen[pr] % len(names[pr])
            seen[pr] += 1
            self.policy.append(names[pr][p])
            self.port[i] = deploy.PORTS[pr]
            frames = FRAMES[pr](rng, p, cfg, v)
            reply = REPLY.get(pr) or encode_dns_query(
                "reply.svc.local", qid=int(rng.integers(0, 1 << 16)))
            corpora.append(frames + [reply])
        # The on_io lane: complete-frame connections, equal per protocol.
        self.lane = np.zeros(n, bool)
        per = int(params.get("on_io_conns", 0)) // len(protos)
        for pr in protos:
            idx = np.flatnonzero((proto == pr) & (cat == COMPLETE))[:per]
            self.lane[idx] = True
        wp = max(len(f) for c in corpora for f in c)
        self.pool_width = wp
        self.pool = np.zeros((n, v + 1, wp), np.uint8)
        self.lens = np.zeros((n, v + 1), np.int64)
        for i, c in enumerate(corpora):
            for j, f in enumerate(c):
                self.pool[i, j, :len(f)] = np.frombuffer(f, np.uint8)
                self.lens[i, j] = len(f)
        self.flat = self.pool.reshape(-1)
        self.picks = rng.integers(0, v, (n, PICKS), dtype=np.int64)
        # Whole-frame connections ride complete-flag matrices, as an edge
        # that frames r2d2 and DNS ships them; everything else goes as
        # data batches.
        fits = self.lens[:, :v].max(axis=1) <= width
        self.whole = ((cat == COMPLETE) & (proto != "http") & fits)
        pol_index = {name: j for j, name in
                     enumerate(sorted(set(self.policy)))}
        self.group = np.array(
            [2 * pol_index[self.policy[i]] + int(self.whole[i])
             for i in range(n)], np.int64)
        # Seed-drawn order in which connections first send.
        self.order = rng.permutation(np.flatnonzero(~self.lane))
        self.lane_order = np.flatnonzero(self.lane)
        self.check_order = rng.permutation(n)

    # -- one push ---------------------------------------------------------

    def push(self, i: int, k: int) -> tuple[bool, bytes]:
        """(reply direction, bytes) of push ``k`` on conn ``i``."""
        (start, ln), (start2, ln2) = self._segments(
            np.array([i]), np.array([k]))
        data = self.flat[start[0]:start[0] + ln[0]].tobytes()
        if ln2[0]:
            data += self.flat[start2[0]:start2[0] + ln2[0]].tobytes()
        return int(self.category[i]) == REPLY, data

    def verdicts(self, idx: np.ndarray, ks: np.ndarray) -> np.ndarray:
        """Verdicts (whole frames judged) each push yields."""
        cat = self.category[idx]
        out = np.ones(len(idx), np.int64)
        out[cat == PIPELINED] = 2
        out[(cat == PARTIAL) & (ks % 2 == 0)] = 0
        return out

    def _segments(self, idx: np.ndarray, ks: np.ndarray):
        """Two (start, length) segments into ``flat`` for each push."""
        v = self.variants
        cat = self.category[idx]
        pk = np.where(cat == PARTIAL, ks // 2,
                      np.where(cat == PIPELINED, 2 * ks, ks)) % PICKS
        var = self.picks[idx, pk]
        var = np.where(cat == REPLY, v, var)
        ln = self.lens[idx, var]
        start = (idx * (v + 1) + var) * self.pool_width
        half = ln // 2
        part = cat == PARTIAL
        second = part & (ks % 2 == 1)
        start = np.where(second, start + half, start)
        ln = np.where(part, np.where(second, ln - half, half), ln)
        pipe = cat == PIPELINED
        var2 = self.picks[idx, (2 * ks + 1) % PICKS]
        ln2 = np.where(pipe, self.lens[idx, var2], 0)
        start2 = (idx * (v + 1) + var2) * self.pool_width
        return (start, ln), (start2, ln2)

    # -- a shim's messages for a set of pushes ----------------------------

    def messages(self, idx: np.ndarray, ks: np.ndarray) -> list[tuple]:
        """Group pushes ``(idx[j], ks[j])`` as each endpoint's shim would:
        per policy one complete-flag matrix of whole frames and one data
        batch of everything else.  Returns ``(kind, sel, args)``: the
        positions ``sel`` of the message's pushes in ``idx``, and
        ``kind`` "matrix" (args: ids, lengths, rows bytes) or "batch"
        (args: ids, flags, lengths, blob)."""
        order = np.argsort(self.group[idx], kind="stable")
        g = self.group[idx[order]]
        cuts = np.flatnonzero(np.diff(g)) + 1
        out = []
        for sel in np.split(order, cuts):
            gi, gk = idx[sel], ks[sel]
            (s1, l1), (s2, l2) = self._segments(gi, gk)
            ids = self.cid[gi]
            if self.whole[gi[0]]:
                rows = np.zeros((len(gi), self.width), np.uint8)
                wp = min(self.pool_width, self.width)
                rows[:, :wp] = self.flat[
                    s1[:, None] + np.arange(wp)[None, :]]
                cols = np.arange(self.width)[None, :]
                rows[cols >= l1[:, None]] = 0
                out.append(("matrix", sel,
                            (ids, l1.astype(np.uint32), rows.tobytes())))
                continue
            starts = np.stack([s1, s2], 1).ravel()
            lens = np.stack([l1, l2], 1).ravel()
            blob = gather(self.flat, starts, lens)
            flags = (self.category[gi] == REPLY).astype(np.uint8)
            out.append(("batch", sel,
                        (ids, flags, (l1 + l2).astype(np.uint32),
                         blob.tobytes())))
        return out


def gather(flat: np.ndarray, starts: np.ndarray,
           lens: np.ndarray) -> np.ndarray:
    """Concatenate ``flat[s:s+l]`` for each segment, in order."""
    total = int(lens.sum())
    if not total:
        return np.zeros(0, np.uint8)
    out_off = np.concatenate(([0], np.cumsum(lens)[:-1]))
    idx = np.repeat(starts - out_off, lens) + np.arange(total)
    return flat[idx]
