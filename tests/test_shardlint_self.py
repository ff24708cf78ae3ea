"""shardlint enforces itself: the AST lint runs over the ENTIRE ray_tpu
package in tier-1 and asserts zero error-severity findings, so every
future PR that introduces a blocking call in an async def or a host sync
in a jitted function fails CI here — with the finding's own message and
fix hint as the failure output."""
from __future__ import annotations

import os

import ray_tpu
from ray_tpu.analysis import errors, format_report, lint_path

PACKAGE_ROOT = os.path.dirname(os.path.abspath(ray_tpu.__file__))


def test_package_has_zero_error_findings():
    findings = lint_path(PACKAGE_ROOT)
    errs = errors(findings)
    assert errs == [], (
        "shardlint found error-severity findings in ray_tpu/ — fix them "
        "or suppress a justified one with `# shardlint: disable=<rule>`:"
        "\n" + format_report(errs))


def test_package_lint_covers_the_whole_tree():
    """The walk actually visits the package (a path bug would vacuously
    pass the self-lint): serve/, parallel/, train/ all contain files the
    linter parsed."""
    seen = set()
    for dirpath, _dirnames, filenames in os.walk(PACKAGE_ROOT):
        if any(n.endswith(".py") for n in filenames):
            seen.add(os.path.relpath(dirpath, PACKAGE_ROOT).split(
                os.sep)[0])
    assert {"serve", "parallel", "train", "resilience", "weights",
            "models", "mpmd", "online"} <= seen


def test_kvcache_module_is_lint_covered():
    """The paged KV cache (models/kvcache.py) is inside the self-lint
    set: the walk parses it and it carries zero error findings of its
    own (a rename/move would silently drop it from coverage)."""
    path = os.path.join(PACKAGE_ROOT, "models", "kvcache.py")
    assert os.path.exists(path)
    assert errors(lint_path(path)) == []


def test_mpmd_package_is_lint_covered():
    """The MPMD pipeline subsystem (ray_tpu/mpmd/) is inside the
    self-lint set: the walk parses it and it carries zero error
    findings of its own (a rename/move would silently drop it from
    coverage)."""
    path = os.path.join(PACKAGE_ROOT, "mpmd")
    assert os.path.isdir(path)
    assert errors(lint_path(path)) == []


def test_online_package_is_lint_covered():
    """The online learning loop (ray_tpu/online/) is inside the
    self-lint set: the walk parses it and it carries zero error
    findings of its own (a rename/move would silently drop it from
    coverage)."""
    path = os.path.join(PACKAGE_ROOT, "online")
    assert os.path.isdir(path)
    assert errors(lint_path(path)) == []


def test_roofline_module_is_lint_covered():
    """The step-time oracle (observability/roofline.py) is inside the
    self-lint set: the walk parses it and it carries zero error
    findings of its own (a rename/move would silently drop it from
    coverage)."""
    path = os.path.join(PACKAGE_ROOT, "observability", "roofline.py")
    assert os.path.exists(path)
    assert errors(lint_path(path)) == []


def test_disagg_modules_are_lint_covered():
    """Disaggregated serving (serve/disagg.py) is inside the
    self-lint set: the walk parses it and it carries zero error
    findings of its own (a rename/move would silently drop it from
    coverage)."""
    for rel in (os.path.join("serve", "disagg.py"),):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        assert errors(lint_path(path)) == [], rel


def test_autoscale_module_is_lint_covered():
    """The serving autoscaler (serve/autoscale.py) is inside the
    self-lint set: the walk parses it and it carries zero error
    findings of its own (a rename/move would silently drop it from
    coverage)."""
    path = os.path.join(PACKAGE_ROOT, "serve", "autoscale.py")
    assert os.path.exists(path)
    assert errors(lint_path(path)) == []


def test_servefault_modules_are_lint_covered():
    """The serving fault-tolerance paths — the failover router + chaos
    ops + chunk-retry plumbing (serve/disagg.py, serve/autoscale.py,
    resilience/chaos.py, util/chunks.py) — are inside the self-lint
    set and carry zero error findings; every bare tier-replica call
    that bypasses the failover wrapper is either routed through
    _tier_call or carries a justification suppression (the
    unsupervised-actor-call rule is INFO, so this asserts the flagged
    count is zero AFTER suppressions)."""
    from ray_tpu.analysis import lint_path as lp

    for rel in (os.path.join("serve", "disagg.py"),
                os.path.join("serve", "autoscale.py"),
                os.path.join("resilience", "chaos.py"),
                os.path.join("util", "chunks.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lp(path)
        assert errors(findings) == [], rel
        bare = [f for f in findings
                if f.rule == "unsupervised-actor-call"]
        assert bare == [], (rel, [str(f) for f in bare])


def test_unsupervised_actor_call_rule_fires():
    """The rule catches a seeded violation: a module importing
    serve.disagg's _call helper and invoking it bare on a replica
    .target outside the failover wrapper."""
    from ray_tpu.analysis.astlint import lint_source

    src = (
        "from ray_tpu.serve.disagg import _call\n"
        "def probe(rep):\n"
        "    return _call(rep.target, 'stats')\n"
        "def probe2(snapshot):\n"
        "    return _call(snapshot['target'], 'stats')\n"
        "def _tier_call(rep):\n"
        "    return _call(rep.target, 'stats')  # sanctioned wrapper\n"
        "def fine(rep):\n"
        "    return _call(rep, 'stats')  # plain handle, not flagged\n"
    )
    found = [f for f in lint_source(src, "seeded.py")
             if f.rule == "unsupervised-actor-call"]
    assert len(found) == 2, [str(f) for f in found]
    assert all(f.severity == "info" for f in found)
    # ...and stays silent in modules without the disagg _call in scope
    other = lint_source("def f(rep):\n    return _call(rep.target)\n",
                        "other.py")
    assert [f for f in other
            if f.rule == "unsupervised-actor-call"] == []


def test_lora_modules_are_lint_covered():
    """Multi-tenant LoRA serving (serve/lora.py, online/lora.py) and
    the modules it rewired (models/engine.py, serve/disagg.py) are
    inside the self-lint set and carry zero error
    findings — and zero unkeyed-tenant-cache findings after
    suppressions (every prefix-cache lookup in lora-aware code passes
    the tenant namespace)."""
    for rel in (os.path.join("serve", "lora.py"),
                os.path.join("online", "lora.py"),
                os.path.join("models", "engine.py"),
                os.path.join("serve", "disagg.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lint_path(path)
        assert errors(findings) == [], rel
        unkeyed = [f for f in findings
                   if f.rule == "unkeyed-tenant-cache"]
        assert unkeyed == [], (rel, [str(f) for f in unkeyed])


def test_unkeyed_tenant_cache_rule_fires():
    """The rule catches a seeded violation: a LoRA-aware module (it
    imports from serve.lora) doing a tenant-blind prefix-cache lookup
    — and honors suppressions, namespace= keywords, and stays silent
    in modules without serve.lora in scope."""
    from ray_tpu.analysis.astlint import lint_source

    src = (
        "from ray_tpu.serve.lora import AdapterPool\n"
        "def bad(kv_cache, toks):\n"
        "    return kv_cache.lookup(toks, max_tokens=7)\n"
        "def bad2(self, toks):\n"
        "    return self.kv_cache.lookup(toks, max_tokens=7)\n"
        "def fine(kv_cache, toks, tenant):\n"
        "    return kv_cache.lookup(toks, max_tokens=7, "
        "namespace=tenant)\n"
        "def unrelated(registry):\n"
        "    return registry.lookup('x')  # not a cache receiver\n"
    )
    found = [f for f in lint_source(src, "seeded.py")
             if f.rule == "unkeyed-tenant-cache"]
    assert len(found) == 2, [str(f) for f in found]
    assert all(f.severity == "info" for f in found)
    # a justified suppression silences it
    suppressed = src.replace(
        "return kv_cache.lookup(toks, max_tokens=7)",
        "return kv_cache.lookup(toks, max_tokens=7)"
        "  # shardlint: disable=unkeyed-tenant-cache")
    left = [f for f in lint_source(suppressed, "seeded.py")
            if f.rule == "unkeyed-tenant-cache"]
    assert len(left) == 1
    # ...and the rule is inert without serve.lora in scope
    other = ("def f(kv_cache, toks):\n"
             "    return kv_cache.lookup(toks, max_tokens=7)\n")
    assert [f for f in lint_source(other, "other.py")
            if f.rule == "unkeyed-tenant-cache"] == []


def test_kvplane_modules_are_lint_covered():
    """The global KV plane (serve/kvplane.py) and the modules it
    rewired (models/kvcache.py, serve/disagg.py, _private/conductor.py)
    are inside the self-lint set and carry zero error findings — and
    zero unregistered-prefix-publish findings after suppressions
    (every chunk-fabric prefix export pairs with the conductor's
    atomic directory commit)."""
    for rel in (os.path.join("serve", "kvplane.py"),
                os.path.join("models", "kvcache.py"),
                os.path.join("serve", "disagg.py"),
                os.path.join("_private", "conductor.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lint_path(path)
        assert errors(findings) == [], rel
        unreg = [f for f in findings
                 if f.rule == "unregistered-prefix-publish"]
        assert unreg == [], (rel, [str(f) for f in unreg])


def test_unregistered_prefix_publish_rule_fires():
    """The rule catches a seeded violation: a KV-plane-aware module
    exporting a prefix into the chunk fabric without the conductor's
    directory commit in scope — and honors the publish_prefix helper,
    the kvplane_publish literal, suppressions, and stays silent in
    modules without kvplane/kvcache in scope."""
    from ray_tpu.analysis.astlint import lint_source

    src = (
        "from ray_tpu.serve import kvplane\n"
        "def bad(worker, cache, toks):\n"
        "    packed, n, dig = cache.export_prefix(toks, None, 32)\n"
        "    return put_tree(worker, packed)  # fabric, no commit\n"
        "def fine_helper(worker, cache, toks):\n"
        "    return kvplane.publish_prefix(worker, cache, toks, None, "
        "'rep')\n"
        "def fine_commit(worker, cache, toks):\n"
        "    packed, n, dig = cache.export_prefix(toks, None, 32)\n"
        "    return worker.conductor.call('kvplane_publish', '', dig, "
        "{})\n"
    )
    found = [f for f in lint_source(src, "seeded.py")
             if f.rule == "unregistered-prefix-publish"]
    assert len(found) == 1, [str(f) for f in found]
    assert found[0].severity == "info"
    assert ":3" in found[0].location
    # a justified suppression silences it
    suppressed = src.replace(
        "packed, n, dig = cache.export_prefix(toks, None, 32)\n"
        "    return put_tree",
        "packed, n, dig = cache.export_prefix(toks, None, 32)"
        "  # shardlint: disable=unregistered-prefix-publish\n"
        "    return put_tree")
    assert [f for f in lint_source(suppressed, "seeded.py")
            if f.rule == "unregistered-prefix-publish"] == []
    # ...and the rule is inert without kvplane/kvcache in scope
    other = ("def f(cache, toks):\n"
             "    return cache.export_prefix(toks, None, 32)\n")
    assert [f for f in lint_source(other, "other.py")
            if f.rule == "unregistered-prefix-publish"] == []


def test_speculation_modules_are_lint_covered():
    """The speculative-decoding + int8-KV modules (models/engine.py,
    models/kvcache.py, serve/lora.py after the donated-write rework)
    are inside the self-lint set, carry zero error findings, and —
    pool-write discipline — zero `undonated-pool-write` findings after
    suppressions: every pool mutation goes through a donated jit."""
    from ray_tpu.analysis import lint_path as lp

    for rel in (os.path.join("models", "engine.py"),
                os.path.join("models", "kvcache.py"),
                os.path.join("serve", "lora.py"),
                os.path.join("serve", "disagg.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lp(path)
        assert errors(findings) == [], rel
        undonated = [f for f in findings
                     if f.rule == "undonated-pool-write"]
        assert undonated == [], (rel, [str(f) for f in undonated])


def test_undonated_pool_write_zero_across_package():
    """No module in the whole package writes a pool outside a donated
    jit (after justified suppressions) — the rule that keeps the
    kvcache/adapter-pool O(row) write discipline from regressing."""
    found = [f for f in lint_path(PACKAGE_ROOT)
             if f.rule == "undonated-pool-write"]
    assert found == [], [str(f) for f in found]


def test_gateway_modules_are_lint_covered():
    """The HTTP front door (serve/gateway.py, serve/qos.py) and the
    other aiohttp-serving modules its rule activates in
    (dashboard/__init__.py) are inside the self-lint set, carry zero
    error findings, and — event-loop discipline — zero
    `sync-io-in-gateway-handler` findings after suppressions: every
    decode in an async handler rides the executor."""
    for rel in (os.path.join("serve", "gateway.py"),
                os.path.join("serve", "qos.py"),
                os.path.join("dashboard", "__init__.py"),
                os.path.join("serve", "disagg.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lint_path(path)
        assert errors(findings) == [], rel
        sync_io = [f for f in findings
                   if f.rule == "sync-io-in-gateway-handler"]
        assert sync_io == [], (rel, [str(f) for f in sync_io])


def test_sync_io_in_gateway_handler_rule_fires():
    """The rule catches a seeded violation: an aiohttp module calling
    .generate()/.decode_from() synchronously inside an async handler —
    and honors suppressions, leaves nested executor defs alone, and
    stays silent in modules that never import aiohttp."""
    from ray_tpu.analysis.astlint import lint_source

    src = (
        "import aiohttp\n"
        "from aiohttp import web\n"
        "async def handler(request):\n"
        "    out = router.generate(prompt, 16)\n"
        "    kv = server.decode_from(rec)\n"
        "    def work():\n"
        "        return router.generate(prompt, 16)  # executor scope\n"
        "    return web.json_response(out)\n"
        "def sync_handler(request):\n"
        "    return router.generate(prompt, 16)  # not async\n"
    )
    found = [f for f in lint_source(src, "seeded.py")
             if f.rule == "sync-io-in-gateway-handler"]
    assert len(found) == 2, [str(f) for f in found]
    assert all(f.severity == "info" for f in found)
    # a justified suppression silences it
    suppressed = src.replace(
        "    kv = server.decode_from(rec)",
        "    kv = server.decode_from(rec)"
        "  # shardlint: disable=sync-io-in-gateway-handler")
    left = [f for f in lint_source(suppressed, "seeded.py")
            if f.rule == "sync-io-in-gateway-handler"]
    assert len(left) == 1
    # ...and the rule is inert without aiohttp in scope
    other = ("async def handler(request):\n"
             "    return router.generate(prompt, 16)\n")
    assert [f for f in lint_source(other, "other.py")
            if f.rule == "sync-io-in-gateway-handler"] == []


def test_requesttrace_modules_are_lint_covered():
    """The flight recorder (observability/requests.py) and the traced
    modules its rule activates in (serve/disagg.py, serve/gateway.py)
    are inside the self-lint set, carry zero error
    findings, and — context discipline — zero
    `unpropagated-request-context` findings after suppressions: every
    cross-tier serve dispatch in a traced module records its hop."""
    for rel in (os.path.join("observability", "requests.py"),
                os.path.join("observability", "timeline.py"),
                os.path.join("serve", "disagg.py"),
                os.path.join("serve", "gateway.py")):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        findings = lint_path(path)
        assert errors(findings) == [], rel
        dropped = [f for f in findings
                   if f.rule == "unpropagated-request-context"]
        assert dropped == [], (rel, [str(f) for f in dropped])


def test_unpropagated_request_context_rule_fires():
    """The rule catches a seeded violation: a module importing the
    request-trace API that dispatches a cross-tier serve call
    (_tier_call/"prefill", _call/"start_decode") from a function scope
    that never touches the trace — and honors suppressions, leaves
    trace-recording scopes alone, and stays silent in modules that
    never import the trace API."""
    from ray_tpu.analysis.astlint import lint_source

    src = (
        "from ray_tpu.observability import requests as reqtrace\n"
        "def blind_prefill(self, pf, ids):\n"
        "    return self._tier_call(pf, 'prefill', 'prefill', ids)\n"
        "def blind_decode(target, rec):\n"
        "    return _call(target, 'start_decode', rec)\n"
        "def traced_prefill(self, pf, ids):\n"
        "    with reqtrace.phase('prefill'):\n"
        "        return self._tier_call(pf, 'prefill', 'prefill', ids)\n"
        "def probe(self, pf):\n"
        "    return self._tier_call(pf, 'prefill', 'describe')\n"
    )
    found = [f for f in lint_source(src, "seeded.py")
             if f.rule == "unpropagated-request-context"]
    assert len(found) == 2, [str(f) for f in found]
    assert all(f.severity == "info" for f in found)
    assert {f.location for f in found} == {"seeded.py:3", "seeded.py:5"}
    # a justified suppression silences it
    suppressed = src.replace(
        "    return _call(target, 'start_decode', rec)",
        "    return _call(target, 'start_decode', rec)"
        "  # shardlint: disable=unpropagated-request-context")
    left = [f for f in lint_source(suppressed, "seeded.py")
            if f.rule == "unpropagated-request-context"]
    assert len(left) == 1
    # ...and the rule is inert without the trace API in scope
    other = ("def blind_prefill(self, pf, ids):\n"
             "    return self._tier_call(pf, 'prefill', 'prefill', ids)\n")
    assert [f for f in lint_source(other, "other.py")
            if f.rule == "unpropagated-request-context"] == []


def test_driver_entry_is_clean_too():
    repo_root = os.path.dirname(PACKAGE_ROOT)
    entry = os.path.join(repo_root, "__graft_entry__.py")
    if os.path.exists(entry):
        assert errors(lint_path(entry)) == []


# ---------------------------------------------------------------------------
# invariant engine (shardlint v2) self-enforcement


def test_invariant_engine_package_gate():
    """The cross-module invariant engine runs over the REAL package in
    tier-1 — the same gate as `python -m ray_tpu analyze --invariants
    --fail-on=error`. Any unsuppressed error-severity invariant finding
    fails CI right here with the finding's own fix hint as the failure
    output."""
    from ray_tpu.analysis import analyze_invariants, format_report

    findings = analyze_invariants(PACKAGE_ROOT)
    errs = errors(findings)
    assert errs == [], (
        "invariant engine found error-severity findings in ray_tpu/:"
        "\n" + format_report(errs))


def test_lock_discipline_clean_across_threaded_modules():
    """The lock-discipline detector stays at zero findings over the
    modules that actually run multi-threaded — the conductor, the
    serving stack (gateway/qos/disagg/autoscale), the online loop and
    the MPMD pipeline. A new bare mutation of a lock-guarded attribute
    in any of them fails here, citing both sites."""
    for rel in (os.path.join("_private", "conductor.py"),
                os.path.join("_private", "telemetry.py"),
                os.path.join("serve", "gateway.py"),
                os.path.join("serve", "qos.py"),
                os.path.join("serve", "disagg.py"),
                os.path.join("serve", "autoscale.py"),
                "online", "mpmd"):
        path = os.path.join(PACKAGE_ROOT, rel)
        assert os.path.exists(path), rel
        bad = [f for f in lint_path(path)
               if f.rule in ("lock-discipline",
                             "undonated-jit-pool-arg")]
        assert bad == [], (rel, [str(f) for f in bad])


def test_env_knob_registry_clean_and_documented():
    """Every RAY_TPU_* read in the tree parses through a cached
    accessor (or is otherwise cold), agrees on its default across
    modules, and appears in the README knob table — the three env-knob
    rules report nothing on the real package."""
    from ray_tpu.analysis.invariants import (check_env_knobs,
                                             collect_env_reads)

    repo_root = os.path.dirname(PACKAGE_ROOT)
    readme = os.path.join(repo_root, "README.md")
    readme_text = None
    if os.path.exists(readme):
        with open(readme, encoding="utf-8") as fh:
            readme_text = fh.read()
    reads = collect_env_reads(PACKAGE_ROOT)
    assert reads, "env-knob scanner found no RAY_TPU_* reads at all"
    findings = [f for f in check_env_knobs(reads, readme_text)]
    assert findings == [], [str(f) for f in findings]
