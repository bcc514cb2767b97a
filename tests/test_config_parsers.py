"""Rule files through libyaml's parser (config/loader.py `_parse_yaml`):
no answer may depend on which parser read the file.

`yaml.CSafeLoader` is libyaml's scanner and parser under the same
Python `SafeConstructor` and `Resolver` as `yaml.SafeLoader`, so —

  (a) the two trees are equal, and what one shares through a YAML
      alias the other shares too, on every file of the benchmark's
      three deployments (chipbench/configs/*.json at rehearse size,
      two seeds), the example config, and every rule document that a
      test of this repo holds as a string;
  (b) the rules loaded are the deployment's own (limit and unit of
      every key against chipbench/deploy.py's arithmetic), and
      `dump()` is equal — under the module as installed and under a
      second copy of it imported with `yaml.__with_libyaml__` patched
      False, which has to fall back to the Python parser;
  (c) a malformed document raises a ConfigError of the same text
      either way: the Python parser's, as before the C parser came;
  (d) the service sets the `config_*` gauges at every load.
"""

import ast
import glob
import importlib.util
import json
import os
import sys
from unittest import mock

import numpy as np
import pytest
import yaml

from chipbench import layers
from chipbench.deploy import Deployment, load_json
from ratelimit_tpu.api import Descriptor, Unit
from ratelimit_tpu.config import loader as installed
from ratelimit_tpu.config.runtime import RuntimeSnapshot
from ratelimit_tpu.service.ratelimit import RateLimitService
from ratelimit_tpu.stats.manager import Manager

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIGS = ["tenants-zipf", "mixed-1m", "uniform-10k-persecond"]
SEEDS = [7, 2**31 + 11]


def _import_without_libyaml():
    """A second copy of config/loader.py, imported as an installation
    whose PyYAML has no libyaml would import it."""
    name = "ratelimit_tpu.config._loader_without_libyaml"
    spec = importlib.util.spec_from_file_location(name, installed.__file__)
    module = importlib.util.module_from_spec(spec)
    # In sys.modules only while it executes (@dataclass looks there).
    with mock.patch.object(yaml, "__with_libyaml__", False), mock.patch.dict(sys.modules, {name: module}):
        spec.loader.exec_module(module)
    return module


FALLBACK = _import_without_libyaml()
LOADERS = [pytest.param(installed, id="installed"), pytest.param(FALLBACK, id="without-libyaml")]
needs_libyaml = pytest.mark.skipif(not yaml.__with_libyaml__, reason="this PyYAML has no libyaml")


# ---------------------------------------------------------------------------
# the documents
# ---------------------------------------------------------------------------


def deployment(config: str, seed: int) -> Deployment:
    return Deployment(load_json("configs", config), seed, rehearse=True)


def deployment_files(dep: Deployment, module) -> list:
    return [module.ConfigFile(f"config.d{d:04d}", dep.yaml(d)) for d in range(dep.n_domains)]


def documents_in_tests() -> list:
    """Every string constant with a `domain:` in it that a test file
    of this repo holds (this file's own among them): the rule
    documents the tests load, sound and malformed alike, and pieces of
    f-strings, which are just more text to parse alike."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        with open(path) as f:
            source = f.read()
        if "domain:" not in source:
            continue
        seen = set()
        for node in ast.walk(ast.parse(source)):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                if "domain:" in node.value and "\n" in node.value and node.value not in seen:
                    seen.add(node.value)
                    out.append(pytest.param(node.value, id=f"{os.path.basename(path)[5:-3]}-{node.lineno}"))
    return out


with open(os.path.join(ROOT, "examples", "ratelimit", "config", "example.yaml")) as _f:
    EXAMPLE = _f.read()

# What tests/test_config.py feeds the loader on one line (the harvest
# above takes only multi-line strings), and documents whose faults the
# YAML parser itself finds: libyaml words each of these differently.
MALFORMED = {
    "empty-domain": "domain: ''\ndescriptors: []",
    "empty-key": "domain: d\ndescriptors: [{value: v}]",
    "bad-unit": "domain: d\ndescriptors: [{key: k, rate_limit: {unit: fortnight, requests_per_unit: 1}}]",
    "unlimited-with-unit": "domain: d\ndescriptors: [{key: k, rate_limit: {unlimited: true, unit: second, requests_per_unit: 1}}]",
    "unknown-key": "domain: d\ndescriptors: [{key: k, ratelimit: {unit: second}}]",
    "nested-unknown-key": "domain: d\ndescriptors: [{key: k, rate_limit: {unit: second, requests_perunit: 1}}]",
    "list-of-non-map": "domain: d\ndescriptors: [not-a-map]",
    "non-string-key": "1: d",
    "non-string-value": "domain: d\ndescriptors: [{key: k, value: 404}]",
    "root-not-a-map": "- domain: d",
    "bad-flow": "domain: d\ndescriptors: [}{",
    "bad-indent": "domain: d\ndescriptors:\n  - key: k\n value: v\n",
    "unclosed-flow": "domain: d\ndescriptors: [{key: k, rate_limit: {unit: second",
    "tab-indentation": "domain: d\ndescriptors:\n\t- key: k\n",
    "bad-alias": "domain: d\ndescriptors: *nowhere\n",
    "duplicate-anchor": "domain: d\ndescriptors:\n  - &a {key: k}\n  - &a {key: l}\n  - *a\n",
    "non-utf-8-bytes": b"domain: d\xff\xfe\ndescriptors: []\n",
    "lone-surrogate": "domain: d\udcff\ndescriptors: []\n",
    "control-character": "domain: d\x07\ndescriptors: []\n",
}
# ... whose error is the YAML parser's, not the loader's own checks.
SYNTAX = [
    "bad-flow", "bad-indent", "unclosed-flow", "tab-indentation", "bad-alias",
    "non-utf-8-bytes", "lone-surrogate", "control-character",
]


def both_trees(document):
    """(libyaml's tree, the Python parser's), or the error each raised."""
    out = []
    for loader in (yaml.CSafeLoader, yaml.SafeLoader):
        try:
            out.append(yaml.load(document, Loader=loader))
        except (yaml.YAMLError, UnicodeError) as e:
            out.append(e)
    return out


def assert_same_shape(a, b, twins: dict) -> None:
    """`a` and `b` are equal node for node, and two places of `a` hold
    the same object exactly where the two places of `b` do."""
    assert type(a) is type(b)
    if not isinstance(a, (dict, list)):
        assert a == b
        return
    if id(a) in twins:
        assert twins[id(a)] == id(b), "shared in one tree, a copy in the other"
        return
    assert id(b) not in twins.values(), "shared in one tree, a copy in the other"
    twins[id(a)] = id(b)
    assert len(a) == len(b)
    if isinstance(a, dict):
        assert list(a) == list(b)  # keys, in file order
        for key in a:
            assert_same_shape(a[key], b[key], twins)
    else:
        for x, y in zip(a, b):
            assert_same_shape(x, y, twins)


def outcome(module, document, name: str = "config.f") -> tuple:
    """What a load of the one document gives under `module`."""
    try:
        cfg = module.load_config([module.ConfigFile(name, document)], Manager())
    except module.ConfigError as e:
        return ("ConfigError", str(e))
    return ("loaded", cfg.dump(), cfg.n_rules, sorted(cfg.priorities.items()))


# ---------------------------------------------------------------------------
# (a) the trees
# ---------------------------------------------------------------------------


def test_the_second_copy_falls_back():
    assert FALLBACK.C_PARSER is False
    assert installed.C_PARSER is bool(yaml.__with_libyaml__)
    assert FALLBACK.ConfigError is not installed.ConfigError  # a module of its own
    with mock.patch.object(yaml, "CSafeLoader", None):  # never reached for
        assert FALLBACK._parse_yaml("a: [1, 2]") == {"a": [1, 2]}


@needs_libyaml
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", CONFIGS)
def test_deployment_trees_equal_aliases_shared(config, seed):
    dep = deployment(config, seed)
    for d in range(dep.n_domains):
        c_tree, py_tree = both_trees(dep.yaml(d))
        assert c_tree == py_tree
        assert_same_shape(c_tree, py_tree, {})
        for fam in dep.families:
            if fam.kind == "nested" and len(fam.path) == 2:
                # The leaf list is written once and aliased from the
                # other groups: ONE list under either parser.
                for tree in (c_tree, py_tree):
                    groups = [g for g in tree["descriptors"] if g["key"] == fam.path[0][0]]
                    assert len(groups) == fam.path[0][1] > 1
                    assert len({id(g["descriptors"]) for g in groups}) == 1


@needs_libyaml
@pytest.mark.parametrize("document", [pytest.param(EXAMPLE, id="example.yaml")] + documents_in_tests())
def test_document_parses_alike(document):
    c_tree, py_tree = both_trees(document)
    if isinstance(py_tree, Exception):
        assert isinstance(c_tree, Exception)  # the texts: test_error_text_*
    else:
        assert_same_shape(c_tree, py_tree, {})
    assert outcome(installed, document) == outcome(FALLBACK, document)


def test_shared_shape_check_sees_a_copy():
    shared = yaml.safe_load("a: &x [1]\nb: *x")
    copied = yaml.safe_load("a: [1]\nb: [1]")
    assert shared == copied
    assert_same_shape(shared, yaml.safe_load("a: &y [1]\nb: *y"), {})
    for a, b in ((shared, copied), (copied, shared)):
        with pytest.raises(AssertionError, match="shared in one tree"):
            assert_same_shape(a, b, {})


# ---------------------------------------------------------------------------
# (b) the rules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("module", LOADERS)
def test_rules_are_the_deployments(module, config, seed):
    dep = deployment(config, seed)
    cfg = module.load_config(deployment_files(dep, module), Manager())
    n_rules = sum(f.count if f.kind == "nested" else 1 for f in dep.families)
    assert cfg.n_rules == dep.n_domains * n_rules == cfg.dump().count("\n")
    assert cfg.parse_s > 0
    k = np.arange(dep.kpd)
    units = dep.unit_s_by_family[dep.family_of(k)]
    unit_of = {1: Unit.SECOND, 60: Unit.MINUTE, 3600: Unit.HOUR, 86400: Unit.DAY}
    for d in range(dep.n_domains):
        limits = dep.limits_of(np.full(dep.kpd, d), k)
        for i in range(dep.kpd):
            rule = cfg.get_limit(dep.domain_name(d), Descriptor.of(*dep.entries(i)))
            assert rule is not None
            assert (rule.limit.requests_per_unit, rule.limit.unit) == (limits[i], unit_of[units[i]])
            assert rule.shadow_mode == bool(dep.shadow_by_family[dep.family_of(k[i : i + 1])[0]])
    reference = FALLBACK.load_config(deployment_files(dep, FALLBACK), Manager())
    assert cfg.dump() == reference.dump()
    assert cfg.priorities == reference.priorities


# ---------------------------------------------------------------------------
# (c) the errors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(MALFORMED))
def test_error_text_identical(name):
    got = outcome(installed, MALFORMED[name], "config.bad")
    assert got[0] == "ConfigError"
    assert got == outcome(FALLBACK, MALFORMED[name], "config.bad")
    assert got[1].startswith("config.bad: ")


@pytest.mark.parametrize("name", SYNTAX)
def test_error_text_is_the_python_parsers(name):
    with pytest.raises(yaml.YAMLError) as python_says:
        yaml.load(MALFORMED[name], Loader=yaml.SafeLoader)
    want = f"config.bad: error loading config file: {python_says.value}"
    assert outcome(installed, MALFORMED[name], "config.bad") == ("ConfigError", want)
    if installed.C_PARSER:
        c_error = both_trees(MALFORMED[name])[0]
        assert isinstance(c_error, Exception) and str(c_error) != str(python_says.value)


@needs_libyaml
def test_a_document_only_libyaml_refuses_still_loads(monkeypatch):
    """Whatever the Python parser accepted before, it accepts now."""
    good = "domain: d\ndescriptors: [{key: k, rate_limit: {unit: second, requests_per_unit: 1}}]"

    class Refuses(yaml.CSafeLoader):
        def get_single_data(self):
            raise yaml.YAMLError("libyaml refuses")

    monkeypatch.setattr(yaml, "CSafeLoader", Refuses)
    assert outcome(installed, good) == outcome(FALLBACK, good)
    assert outcome(installed, good)[0] == "loaded"


# ---------------------------------------------------------------------------
# (d) the gauges
# ---------------------------------------------------------------------------


class _Runtime:
    def __init__(self, files: dict):
        self.files = files

    def snapshot(self):
        return RuntimeSnapshot(self.files)

    def add_update_callback(self, fn):
        pass


def test_service_sets_the_config_gauges():
    dep = deployment("tenants-zipf", SEEDS[0])
    runtime = _Runtime({f"config.d{d}": dep.yaml(d) for d in range(dep.n_domains)})
    manager = Manager()
    service = RateLimitService(runtime, cache=None, stats_manager=manager)
    scope = "ratelimit.service.config_"

    def gauges():
        return {k[len(scope):]: v for k, v in manager.store.snapshot().items() if k.startswith(scope)}

    got = gauges()
    assert set(got) == {"load_success", "load_error", "load_ms", "parse_ms", "rules", "c_parser"}
    built = service.get_current_config().dump().count("\n")
    assert got["rules"] == built == dep.n_domains * 100
    assert got["c_parser"] == int(bool(yaml.__with_libyaml__))
    assert 0 <= got["parse_ms"] <= got["load_ms"]

    # A reload sets them anew; a failed one leaves them (and the config).
    del runtime.files["config.d0"]
    service.reload_config()
    assert gauges()["rules"] == built - 100
    runtime.files["config.bad"] = MALFORMED["bad-indent"]
    service.reload_config()
    assert gauges()["rules"] == built - 100 and gauges()["load_error"] == 1
    assert service.get_current_config().dump().count("\n") == built - 100


@pytest.mark.parametrize(
    "name, want",
    [("config_load_us_per_rule.paced", 1000 * 1500 / 100_000), ("config_parse_share.paced", 100 * 600 / 1500)],
)
def test_benchmark_metric_reads_the_gauges_and_is_silent_on_the_parent(name, want):
    """The driver lays this PR's benchmark files over the parent too,
    which has no such gauge: nothing to report there, and no raise."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["moves"], entry["workloads"]) == ("whole server", "setup_s", ["tenants-zipf.paced"])
    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"} and spec["reader"]["kind"] == "level"
    parent = {"ratelimit.service.config_load_success": 1}
    change = {
        **parent, "ratelimit.service.config_load_ms": 1500, "ratelimit.service.config_parse_ms": 600,
        "ratelimit.service.config_rules": 100_000, "ratelimit.service.config_c_parser": 1,
    }
    assert layers.read(spec["reader"], {"stats_a": {"stats": parent}, "stats_b": {"stats": parent}}) is None
    assert layers.read(spec["reader"], {"stats_b": {"stats": change}}) == pytest.approx(want)
