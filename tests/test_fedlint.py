# Copyright 2026 The rayfed-tpu Authors.
#
# Licensed under the Apache License, Version 2.0 (the "License");
# you may not use this file except in compliance with the License.
# You may obtain a copy of the License at
#
#     http://www.apache.org/licenses/LICENSE-2.0
#
# Unless required by applicable law or agreed to in writing, software
# distributed under the License is distributed on an "AS IS" BASIS,
# WITHOUT WARRANTIES OR CONDITIONS OF ANY KIND, either express or implied.
# See the License for the specific language governing permissions and
# limitations under the License.

"""fedlint: the static analyzer's contract with this repo.

Three layers are pinned here:

1. the fixture corpus in ``tests/lint_fixtures/`` — every seeded-bad
   fixture produces exactly its rule's findings, every good fixture and
   the suppression fixture lint clean;
2. the shipped ``examples/`` drivers stay lint-clean (the analyzer's
   false-positive budget on real drivers is zero);
3. the machine-readable rule anchors in ``rayfed_tpu/api.py``,
   ``rayfed_tpu/parallel/train.py`` and ``rayfed_tpu/proxy/barriers.py``
   name rules that actually exist in the registry.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from rayfed_tpu.lint import ALL_RULES, lint_file, lint_paths, rule_by_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
EXAMPLES = os.path.join(REPO, "examples")

#: fixture file -> (rule id, expected finding count)
BAD_FIXTURES = {
    "bad_perimeter.py": ("FED001", 2),
    "bad_seq_divergence.py": ("FED002", 2),
    "bad_donation_aliasing.py": ("FED003", 1),
    "bad_dangling_fedobject.py": ("FED004", 2),
    "bad_reserved_seq_id.py": ("FED005", 2),
    "bad_insecure_aggregate.py": ("FED006", 2),
    "bad_cross_party_deadlock.py": ("FED007", 2),
    "bad_global_mutable_singleton.py": ("FED008", 2),
    "bad_unvalidated_config_key.py": ("FED009", 2),
    "bad_blocking_in_reactor.py": ("FED010", 2),
    "bad_lock_order.py": ("FED011", 2),
}

GOOD_FIXTURES = [
    "good_perimeter.py",
    "good_seq_divergence.py",
    "good_donation_aliasing.py",
    "good_dangling_fedobject.py",
    "good_reserved_seq_id.py",
    "good_insecure_aggregate.py",
    "good_cross_party_deadlock.py",
    "good_global_mutable_singleton.py",
    "good_unvalidated_config_key.py",
    "good_blocking_in_reactor.py",
    "good_lock_order.py",
    "suppressed.py",
]


def _fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


@pytest.mark.parametrize("name,rule_id,count", [
    (name, rule_id, count)
    for name, (rule_id, count) in sorted(BAD_FIXTURES.items())
])
def test_bad_fixture_caught(name, rule_id, count):
    findings, errors = lint_file(_fixture(name))
    assert not errors, errors
    assert [f.rule_id for f in findings] == [rule_id] * count, [
        f.render() for f in findings
    ]


@pytest.mark.parametrize("name", GOOD_FIXTURES)
def test_good_fixture_clean(name):
    findings, errors = lint_file(_fixture(name))
    assert not errors, errors
    assert not findings, [f.render() for f in findings]


def test_every_rule_has_positive_and_negative_fixture():
    """Adding a rule without corpus coverage is a test failure, not a
    silent gap."""
    covered = {rule_id for rule_id, _ in BAD_FIXTURES.values()}
    assert covered == {r.rule_id for r in ALL_RULES}
    names = set(os.listdir(FIXTURES))
    for bad in BAD_FIXTURES:
        assert bad.replace("bad_", "good_") in names


def test_examples_lint_clean():
    result = lint_paths([EXAMPLES])
    assert len(result.files) == 5, result.files
    assert not result.errors, [e.render() for e in result.errors]
    assert not result.findings, [f.render() for f in result.findings]
    assert result.exit_code == 0


def _run_cli(*args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "rayfed_tpu.lint", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
    )


@pytest.mark.parametrize("name", sorted(BAD_FIXTURES))
def test_cli_exit_1_on_bad_fixture(name):
    proc = _run_cli(_fixture(name))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert BAD_FIXTURES[name][0] in proc.stdout


def test_cli_exit_0_on_examples():
    proc = _run_cli(EXAMPLES)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no findings" in proc.stdout


def test_cli_exit_2_without_paths_or_on_syntax_error(tmp_path):
    assert _run_cli().returncode == 2
    broken = tmp_path / "broken.py"
    broken.write_text("def oops(:\n")
    proc = _run_cli(str(broken))
    assert proc.returncode == 2, proc.stdout + proc.stderr


def test_cli_json_format(tmp_path):
    proc = _run_cli("--format", "json", _fixture("bad_reserved_seq_id.py"))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["version"] == 1
    assert {f["rule_id"] for f in payload["findings"]} == {"FED005"}
    for f in payload["findings"]:
        assert {"path", "line", "col", "rule_id", "rule_name", "message"} <= set(f)


def test_cli_sarif_format():
    proc = _run_cli("--format", "sarif", _fixture("bad_lock_order.py"))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["version"] == "2.1.0"
    run = payload["runs"][0]
    assert run["tool"]["driver"]["name"] == "fedlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {r.rule_id for r in ALL_RULES} <= rule_ids
    results = run["results"]
    assert {r["ruleId"] for r in results} == {"FED011"}
    for r in results:
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad_lock_order.py")
        assert loc["region"]["startLine"] >= 1
        assert r["message"]["text"]


def test_cli_singleton_inventory(tmp_path):
    out = tmp_path / "inventory.json"
    proc = _run_cli(
        _fixture("bad_global_mutable_singleton.py"),
        "--singleton-inventory", str(out),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text())
    assert payload["version"] == 1
    names = {s["name"] for s in payload["singletons"]}
    assert names == {"_round_cache", "_cache_lock"}
    for s in payload["singletons"]:
        assert {"module", "path", "name", "line", "kind", "value",
                "mutators"} <= set(s)


def test_repo_singleton_inventory_is_fresh(tmp_path):
    """tools/singleton_inventory.json (the multi-tenant worklist) must
    match what the detector reports today — regenerate it when module
    globals are added or removed. What is compared is what that says:
    the singletons (module, path, name, kind, value) and how many
    mutators each has, not the lines they stand on (a PR that moves a
    line above one need not regenerate the file)."""
    out = tmp_path / "inventory.json"
    # Relative path on purpose: the committed inventory stores
    # repo-relative paths (the CLI runs with cwd=REPO here).
    proc = _run_cli("rayfed_tpu", "--singleton-inventory", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    def worklist(payload):
        assert payload["version"] == 1
        return sorted(
            (s["module"], s["path"], s["name"], s["kind"], s["value"],
             len(s["mutators"]))
            for s in payload["singletons"]
        )

    with open(os.path.join(REPO, "tools", "singleton_inventory.json")) as f:
        committed = json.load(f)
    assert worklist(json.loads(out.read_text())) == worklist(committed), (
        "tools/singleton_inventory.json is stale; regenerate with "
        "`python -m rayfed_tpu.lint rayfed_tpu --singleton-inventory "
        "tools/singleton_inventory.json`"
    )


def test_self_lint_is_clean():
    """The framework lints itself clean: every finding is either fixed
    or suppressed in place with a justification."""
    result = lint_paths([os.path.join(REPO, "rayfed_tpu")])
    assert not result.errors, [e.render() for e in result.errors]
    assert not result.findings, [f.render() for f in result.findings]


def test_schema_matches_config_dataclasses():
    """lint/schema.py is a static mirror of the runtime config
    dataclasses; this is the tripwire that keeps them in sync."""
    import dataclasses
    import importlib

    from rayfed_tpu.lint import schema

    modules = {
        "CheckpointConfig": "rayfed_tpu.checkpoint",
        "CrossSiloMessageConfig": "rayfed_tpu.config",
        "FailoverConfig": "rayfed_tpu.membership.config",
        "LivenessConfig": "rayfed_tpu.resilience.liveness",
        "MembershipConfig": "rayfed_tpu.config",
        "PartyMeshConfig": "rayfed_tpu.config",
        "PrivacyConfig": "rayfed_tpu.privacy.config",
        "RetryPolicy": "rayfed_tpu.resilience.retry",
        "ServingConfig": "rayfed_tpu.config",
        "TcpCrossSiloMessageConfig": "rayfed_tpu.config",
        "TelemetryConfig": "rayfed_tpu.telemetry.config",
        "TenancyConfig": "rayfed_tpu.tenancy.context",
    }
    assert set(modules) == set(schema.CONFIG_CLASS_FIELDS)
    for name, module in modules.items():
        cls = getattr(importlib.import_module(module), name)
        real = {f.name for f in dataclasses.fields(cls)}
        mirror = set(schema.CONFIG_CLASS_FIELDS[name])
        assert mirror == real, (
            f"lint/schema.py CONFIG_CLASS_FIELDS[{name!r}] is out of "
            f"sync: extra={sorted(mirror - real)} "
            f"missing={sorted(real - mirror)}"
        )


def test_cli_disable_silences_rule():
    proc = _run_cli("--disable", "reserved-seq-id",
                    _fixture("bad_reserved_seq_id.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_rule_registry_metadata():
    ids = [r.rule_id for r in ALL_RULES]
    assert ids == sorted(ids) and len(ids) == len(set(ids))
    for rule in ALL_RULES:
        assert rule.rule_id.startswith("FED") and rule.name and rule.summary
        assert rule_by_id(rule.rule_id) is rule


def test_api_anchors_name_real_rules():
    from rayfed_tpu.api import FEDLINT_ANCHORS

    known = {r.rule_id for r in ALL_RULES}
    assert set(FEDLINT_ANCHORS) == {"get", "remote", "aggregate"}
    for entry, rule_ids in FEDLINT_ANCHORS.items():
        assert rule_ids, entry
        assert set(rule_ids) <= known, (entry, rule_ids)


def test_barriers_anchor_matches_registry():
    from rayfed_tpu.proxy import barriers

    rule = rule_by_id(barriers.FEDLINT_RESERVED_SEQ_RULE)
    assert rule is not None and rule.name == "reserved-seq-id"


def test_train_anchor_matches_registry():
    # Parsed from source rather than imported: train.py pulls in the
    # full jax/optax stack, which this unit test doesn't need.
    path = os.path.join(REPO, "rayfed_tpu", "parallel", "train.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    values = [
        node.value.value
        for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and isinstance(node.value, ast.Constant)
        and any(
            isinstance(t, ast.Name) and t.id == "FEDLINT_DONATION_RULE"
            for t in node.targets
        )
    ]
    assert values == ["FED003"]
    rule = rule_by_id(values[0])
    assert rule is not None and rule.name == "donation-aliasing"
