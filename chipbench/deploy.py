"""A configuration file -> the deployment as it is run: the rule YAML
the server loads and the key universe the traffic draws from.

A configuration (`configs/<name>.json`) lists rule *families*; every
domain of the deployment carries all of them:

  nested  `path` [[key, n], [key, m]]: n x m fully specified
          descriptors ((key, v_i), (key, w_j)), one rule and one counter
          each (upstream README Example 4 style).  `limits` is a list;
          leaf j of domain d gets limits[(j + d + seed) % len].
  keyed   a key-only rule: every distinct value is a counter of its own
          under the one `limit`; `keys` values exist per domain.

A key is numbered `k` inside its domain (families in file order) and
`gid = d * keys_per_domain + k` across the deployment.  Everything here
is a pure function of the file and the seed; nothing reads a clock.
"""

from __future__ import annotations

import bisect
import json
import os
import random

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
UNIT_SECONDS = {"second": 1, "minute": 60, "hour": 3600, "day": 86400}


def load_json(kind: str, name: str) -> dict:
    """`chipbench/<kind>/<name>.json`, found by the name in BENCHMARK.json."""
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


class Family:
    def __init__(self, spec: dict, offset: int, small: bool):
        self.name = spec["name"]
        self.unit = spec["unit"]
        self.unit_s = UNIT_SECONDS[self.unit]
        self.shadow = bool(spec.get("shadow", False))
        self.load = bool(spec.get("load", False))
        self.offset = offset
        if "path" in spec:
            self.kind = "nested"
            self.path = [(k, int(n)) for k, n in spec["path"]]
            if not 1 <= len(self.path) <= 2:
                raise ValueError(f"family {self.name}: path of 1 or 2 levels")
            self.limits = [int(x) for x in spec["limits"]]
            self.n_leaf = self.path[-1][1]
            self.count = int(np.prod([n for _, n in self.path]))
        else:
            self.kind = "keyed"
            self.key = spec["key"]
            self.limits = [int(spec["limit"])]
            keys = int(spec["keys"])
            self.count = max(8, keys // 256) if small else keys


class Deployment:
    """One configuration under one seed."""

    def __init__(self, config: dict, seed: int, rehearse: bool = False, limit_offset: int = 0):
        self.config = config
        self.seed = int(seed)
        rng = random.Random(self.seed)
        self.tag = f"{rng.getrandbits(32):08x}"
        self.shift = rng.randrange(1 << 16)
        # limit_offset is the must-fail control: the SERVER's YAML gets
        # every limit raised by it while the reference keeps the
        # configuration's own (run.py --control server).
        self.limit_offset = int(limit_offset)
        doms = config["domains"]
        self.n_domains = int(doms.get("rehearse_count", 4) if rehearse else doms["count"])
        self.families = []
        offset = 0
        for spec in config["families"]:
            fam = Family(spec, offset, rehearse)
            self.families.append(fam)
            offset += fam.count
        self.kpd = offset  # keys per domain
        self.n_keys = self.n_domains * self.kpd
        self._fam_starts = [f.offset for f in self.families]
        self._fam_offsets = np.array([f.offset for f in self.families] + [self.kpd])
        self.unit_s_by_family = np.array([f.unit_s for f in self.families], dtype=np.int64)
        self.shadow_by_family = np.array([f.shadow for f in self.families], dtype=bool)

    # -- names -----------------------------------------------------------

    def domain_name(self, d: int) -> str:
        return f"{self.config['domains'].get('prefix', 'd')}{self.tag}x{d:04d}"

    def entries(self, k: int) -> list:
        """The (key, value) pairs of key `k` (the same in every domain)."""
        fam = self.families[bisect.bisect_right(self._fam_starts, k) - 1]
        sub = k - fam.offset
        if fam.kind == "keyed":
            return [(fam.key, f"{self.tag}-{sub}")]
        if len(fam.path) == 1:
            return [(fam.path[0][0], f"v{sub}")]
        (k0, _), (k1, n1) = fam.path
        return [(k0, f"g{sub // n1}"), (k1, f"v{sub % n1}")]

    # -- vectorised properties of keys -----------------------------------

    def family_of(self, k: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._fam_offsets, k, side="right") - 1

    def limits_of(self, d: np.ndarray, k: np.ndarray) -> np.ndarray:
        """The configuration's limit of key k in domain d (no offset)."""
        fam_idx = self.family_of(k)
        out = np.zeros(len(k), dtype=np.int64)
        for i, fam in enumerate(self.families):
            m = fam_idx == i
            if not m.any():
                continue
            if fam.kind == "keyed":
                out[m] = fam.limits[0]
            else:
                leaf = (k[m] - fam.offset) % fam.n_leaf
                lim = np.array(fam.limits, dtype=np.int64)
                out[m] = lim[(leaf + d[m] + self.shift) % len(lim)]
        return out

    def load_keys(self) -> np.ndarray:
        """Per-domain key numbers the set-up hits once each."""
        parts = [
            np.arange(f.offset, f.offset + f.count) for f in self.families if f.load
        ]
        return np.concatenate(parts) if parts else np.zeros(0, dtype=np.int64)

    # -- the rule YAML ---------------------------------------------------

    def _rule(self, indent: str, key: str, value, unit: str, limit: int, shadow: bool) -> str:
        out = f"{indent}- key: {key}\n"
        if value is not None:
            out += f"{indent}  value: {value}\n"
        out += (
            f"{indent}  rate_limit:\n{indent}    unit: {unit}\n"
            f"{indent}    requests_per_unit: {limit + self.limit_offset}\n"
        )
        if shadow:
            out += f"{indent}  shadow_mode: true\n"
        return out

    def yaml(self, d: int) -> str:
        """Domain d's file.  The leaf list of a two-level family is
        written once and referred to by a YAML alias from the other
        groups: the loader builds a rule of its own for every path, and
        the pure-Python YAML parse (most of the server's start at 100k
        rules) shrinks by the group count."""
        out = [f"domain: {self.domain_name(d)}\ndescriptors:\n"]
        dd = np.array([d])
        for fam in self.families:
            if fam.kind == "keyed":
                out.append(self._rule("  ", fam.key, None, fam.unit, fam.limits[0], fam.shadow))
                continue
            leaf_key, n_leaf = fam.path[-1]
            lims = self.limits_of(np.repeat(dd, n_leaf), fam.offset + np.arange(n_leaf))
            indent = "  " if len(fam.path) == 1 else "      "
            leaves = "".join(
                self._rule(indent, leaf_key, f"v{j}", fam.unit, int(lims[j]), fam.shadow)
                for j in range(n_leaf)
            )
            if len(fam.path) == 1:
                out.append(leaves)
                continue
            group_key, n_group = fam.path[0]
            anchor = f"{fam.name}_leaves"
            for g in range(n_group):
                out.append(f"  - key: {group_key}\n    value: g{g}\n")
                out.append(
                    f"    descriptors: &{anchor}\n{leaves}" if g == 0
                    else f"    descriptors: *{anchor}\n"
                )
        return "".join(out)

    def write_runtime(self, root: str) -> None:
        cfg = os.path.join(root, "ratelimit", "config")
        os.makedirs(cfg, exist_ok=True)
        for d in range(self.n_domains):
            with open(os.path.join(cfg, f"d{d:04d}.yaml"), "w") as f:
                f.write(self.yaml(d))
