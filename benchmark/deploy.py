"""A configuration's policy set, as plain data.

The rule generators are copied from the repository's stress set
(BASELINE config 5: 250 HTTP policies x 20 rules, 12 literal, 6
regex-tier, 2 of whose automata exceed the DFA budget, and 50 DNS
policies x (16 exact + 4 pattern) rules) and from the r2d2 policy of
``proxylib_test.go`` (READ of /public/ files and HALT are allowed).

``policies(cfg)`` returns a list of dicts that both sides read: the load
generator turns them into the program's ``NetworkPolicy`` objects to
push over the wire, and ``reference.py`` compiles them with ``re``.
Nothing here imports the program.
"""

from __future__ import annotations

# Ports the deployment's listeners use, by protocol.
PORTS = {"http": 80, "dns": 53, "r2d2": 80}


def regex_path(j: int) -> str:
    # Policy-independent text, so every policy's automaton has one shape.
    return f"/g{j:02d}/[a-z0-9]+/item/.*"


def nfa_path(j: int) -> str:
    # (a|b)*a(a|b){7}: its minimal DFA remembers 8 symbols (256 states),
    # past the 128-state budget, so the compiler must keep the NFA.
    return f"/n{j:02d}/(a|b)*a{'(a|b)' * 7}/x"


def dns_name(p: int, j: int) -> str:
    return f"s{j:02d}.p{p:03d}.svc.local"


def dns_pattern(j: int) -> str:
    return f"*.w{j:02d}.svc.local"


def policy_names(cfg: dict) -> dict[str, list[str]]:
    """Policy names by protocol, in the order connections are bound."""
    return {
        "http": [f"http-{p:03d}" for p in range(cfg.get("http_policies", 0))],
        "dns": [f"dns-{p:03d}" for p in range(cfg.get("dns_policies", 0))],
        "r2d2": ["r2d2"] if cfg.get("r2d2_policies", 0) else [],
    }


def policies(cfg: dict) -> list[dict]:
    """Every policy of the configuration: {name, id, proto, port, rules}."""
    out = []
    n_lit = cfg.get("http_literal_rules", 0)
    for p in range(cfg.get("http_policies", 0)):
        paths = (
            [f"/svc{p:03d}/r{j:02d}/.*" for j in range(n_lit)]
            + [regex_path(j) for j in range(cfg["http_dfa_rules"])]
            + [nfa_path(j) for j in range(cfg["http_nfa_rules"])]
        )
        out.append({
            "name": f"http-{p:03d}", "id": 1000 + p, "proto": "http",
            "port": PORTS["http"],
            "rules": [{"method": "GET", "path": path} for path in paths],
        })
    for p in range(cfg.get("dns_policies", 0)):
        rules = (
            [{"matchName": dns_name(p, j)}
             for j in range(cfg["dns_exact_rules"])]
            + [{"matchPattern": dns_pattern(j)}
               for j in range(cfg["dns_pattern_rules"])]
        )
        out.append({
            "name": f"dns-{p:03d}", "id": 2000 + p, "proto": "dns",
            "port": PORTS["dns"], "rules": rules,
        })
    if cfg.get("r2d2_policies", 0):
        out.append({
            "name": "r2d2", "id": 2, "proto": "r2d2", "port": PORTS["r2d2"],
            "rules": [{"cmd": "READ", "file": "/public/.*"}, {"cmd": "HALT"}],
        })
    return out


def network_policies(cfg: dict) -> list:
    """The same policies as the program's ``NetworkPolicy`` objects (what
    an agent pushes over the wire)."""
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )

    out = []
    for pol in policies(cfg):
        if pol["proto"] == "http":
            rule = PortNetworkPolicyRule(http_rules=pol["rules"])
        else:
            rule = PortNetworkPolicyRule(l7_proto=pol["proto"],
                                         l7_rules=pol["rules"])
        out.append(NetworkPolicy(
            name=pol["name"], policy=pol["id"],
            ingress_per_port_policies=[
                PortNetworkPolicy(port=pol["port"], rules=[rule]),
            ],
        ))
    return out
