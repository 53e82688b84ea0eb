"""graftcheck (paddle_tpu/analysis): every shipped rule must FIRE on a
planted violation and stay SILENT on the idiomatic negative; the
analyzer's tier-1 self-run over paddle_tpu/ (src profile) and tests/
(test profile) must be clean and fast; the CLI must honor the
format/exit-code contract CI gates on."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis import Finding, UsageError, run_paths, screen_step_fn
from paddle_tpu.analysis.cli import main as cli_main
from paddle_tpu.analysis.core import SourceFile, run_files

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")
TESTS = os.path.join(REPO, "tests")


def check_src(src, rules, rel="sample.py", extra_files=()):
    """Run `rules` over an in-memory module (plus optional companions
    for cross-file collection); returns findings."""
    files = [SourceFile(rel, src, rel)]
    for erel, esrc in extra_files:
        files.append(SourceFile(erel, esrc, erel))
    return [f for f in run_files(files, rule_ids=list(rules))
            if f.path == rel]


# ---------------------------------------------------------------------------
# capture-safety
# ---------------------------------------------------------------------------

class TestCaptureSafetyRule:
    def _screen(self, body):
        src = ("import paddle_tpu as paddle\n"
               "@paddle.jit_step\n"
               "def step(x, flag):\n"
               + "".join(f"    {ln}\n" for ln in body))
        return check_src(src, ["capture-safety"])

    def test_host_branch_on_tensor_fires(self):
        fs = self._screen(["loss = net(x).sum()",
                           "if float(loss) > 0:",
                           "    loss = loss * 2",
                           "loss.backward()"])
        assert any("host control flow" in f.message for f in fs)

    def test_numpy_item_coercions_fire(self):
        fs = self._screen(["loss = net(x).sum()",
                           "loss.backward()",
                           "v = loss.numpy()",
                           "w = loss.item()"])
        assert sum("host coercion" in f.message for f in fs) == 2

    def test_param_coercion_without_evidence_is_clean(self):
        # a bare parameter is NOT tensor evidence: step args may be
        # host-side np.ndarrays (kept host-side until the jit boundary),
        # and a screen false positive permanently costs the fast path —
        # the dynamic probe owns this case
        fs = self._screen(["y = x.numpy()",
                           "loss = net(x).sum()",
                           "loss.backward()"])
        assert fs == []

    def test_hook_and_create_graph_fire(self):
        fs = self._screen(["loss = net(x).sum()",
                           "loss.register_hook(lambda g: g)",
                           "g = paddle.grad(loss, p, create_graph=True)",
                           "loss.backward()"])
        assert any("hooks" in f.message for f in fs)
        assert any("create_graph" in f.message for f in fs)

    def test_branch_on_plain_python_value_is_clean(self):
        # the do_sched shape: branching on a non-tensor arg must never
        # cost the user the captured path
        fs = self._screen(["loss = net(x).sum()",
                           "loss.backward()",
                           "if flag:",
                           "    sched.step()",
                           "return loss"])
        assert fs == []

    def test_coercion_hidden_in_helper_is_clean(self):
        # the screen never follows calls: dynamic machinery owns this
        fs = self._screen(["loss = net(x).sum()",
                           "loss = helper(loss)",
                           "loss.backward()"])
        assert fs == []

    def test_float_on_untainted_local_is_clean(self):
        fs = self._screen(["lr = float(opt.get_lr())",
                           "loss = net(x).sum()",
                           "loss.backward()"])
        assert fs == []

    def test_taint_propagates_through_assignment(self):
        fs = self._screen(["loss = net(x).sum()",
                           "loss.backward()",
                           "scaled = loss * 3",
                           "if scaled > 0:",
                           "    pass"])
        assert any("host control flow" in f.message for f in fs)

    def test_only_jit_step_functions_screened(self):
        src = ("def free_fn(x):\n"
               "    loss = f(x)\n"
               "    loss.backward()\n"
               "    return float(loss)\n")
        assert check_src(src, ["capture-safety"]) == []


class TestScreenStepFnRuntime:
    def test_live_function_screens_with_real_location(self):
        def doomed(x):
            loss = x.sum()
            loss.backward()
            return float(loss)

        fs = screen_step_fn(doomed)
        assert fs and fs[0].rule == "capture-safety"
        assert fs[0].path.endswith("test_analysis.py")
        assert fs[0].line > 0

    def test_clean_function_returns_empty(self):
        def fine(x):
            loss = x.sum()
            loss.backward()
            return loss

        assert screen_step_fn(fine) == []

    def test_unscreenable_callable_fails_open(self):
        assert screen_step_fn(np.sum) == []
        assert screen_step_fn(lambda x: float(x)) == []


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

class TestDonationSafetyRule:
    def test_read_after_donate_fires(self):
        src = ("import jax\n"
               "def f(state, grads):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    out = jfn(state, grads)\n"
               "    return state.sum()\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1 and "`state`" in fs[0].message

    def test_same_statement_rebind_is_clean(self):
        src = ("import jax\n"
               "def f(state):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    state = jfn(state)\n"
               "    return state.sum()\n")
        assert check_src(src, ["donation-safety"]) == []

    def test_branch_arms_do_not_cross_poison(self):
        # the step_capture hook/no-hook shape: a call in one arm must
        # not poison the other arm's identical call
        src = ("import jax\n"
               "def f(state, hook):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    if hook:\n"
               "        out = jfn(state)\n"
               "    else:\n"
               "        out = jfn(state)\n"
               "    return out\n")
        assert check_src(src, ["donation-safety"]) == []

    def test_read_after_merged_branches_fires(self):
        src = ("import jax\n"
               "def f(state, hook):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    if hook:\n"
               "        out = jfn(state)\n"
               "    else:\n"
               "        out = jfn(state)\n"
               "    return state.sum()\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1

    def test_exception_handler_sees_donation(self):
        src = ("import jax\n"
               "def f(state):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    try:\n"
               "        out = jfn(state)\n"
               "    except Exception:\n"
               "        return state.mean()\n"
               "    return out\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1 and "state" in fs[0].message

    def test_cross_method_attribute_donor(self):
        # the jit/api.py shape: donor bound in _build, called elsewhere
        src = ("import jax\n"
               "class T:\n"
               "    def build(self):\n"
               "        self._fn = jax.jit(step, donate_argnums=(1,))\n"
               "    def call(self, a, b):\n"
               "        out = self._fn(a, b)\n"
               "        return b.sum()\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1 and "`b`" in fs[0].message

    def test_read_with_store_in_same_later_statement_fires(self):
        # `state = state * 2` after a donation READS the dead buffer
        # before rebinding — the store must not hide the read
        src = ("import jax\n"
               "def f(state):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    out = jfn(state)\n"
               "    state = state * 2\n"
               "    return state\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1 and fs[0].line == 5

    def test_rebind_clears_consumption(self):
        src = ("import jax\n"
               "def f(state):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    out = jfn(state)\n"
               "    state = out[0]\n"
               "    return state.sum()\n")
        assert check_src(src, ["donation-safety"]) == []

    def test_undonated_positions_are_clean(self):
        src = ("import jax\n"
               "def f(state, x):\n"
               "    jfn = jax.jit(step, donate_argnums=(0,))\n"
               "    out = jfn(state, x)\n"
               "    return x.sum()\n")
        assert check_src(src, ["donation-safety"]) == []


# ---------------------------------------------------------------------------
# trace-purity
# ---------------------------------------------------------------------------

class TestTracePurityRule:
    REL = "paddle_tpu/ops/kernels/pallas/sample_kernel.py"

    def test_forbidden_calls_fire_in_confined_paths(self):
        src = ("import time\nimport numpy as np\n"
               "def kernel(x):\n"
               "    t0 = time.time()\n"
               "    noise = np.random.randn(4)\n"
               "    flags.set_flags({'benchmark': True})\n"
               "    return x\n")
        fs = check_src(src, ["trace-purity"], rel=self.REL)
        msgs = " | ".join(f.message for f in fs)
        assert len(fs) == 3
        assert "time.time" in msgs and "np.random" in msgs \
            and "set_flags" in msgs

    def test_bump_mesh_epoch_is_allowed(self):
        src = ("def ctx(mesh):\n"
               "    _flags.bump_mesh_epoch()\n")
        assert check_src(src, ["trace-purity"], rel=self.REL) == []

    def test_host_side_files_out_of_scope(self):
        src = ("import time\n"
               "def epoch_timer():\n"
               "    return time.time()\n")
        assert check_src(src, ["trace-purity"],
                         rel="paddle_tpu/hapi/callbacks.py") == []


# ---------------------------------------------------------------------------
# durability (resilience file writes must ride the commit protocol)
# ---------------------------------------------------------------------------

class TestDurabilityRule:
    REL = "paddle_tpu/serving/resilience/journal.py"
    REL_CKPT = "paddle_tpu/distributed/resilience/checkpointer.py"

    def test_bare_open_for_write_fires(self):
        src = ("def save(path, payload):\n"
               "    with open(path, 'w') as f:\n"
               "        f.write(payload)\n")
        fs = check_src(src, ["durability"], rel=self.REL)
        assert len(fs) == 1 and "fsync_write" in fs[0].message

    def test_append_and_mode_kw_fire_in_both_trees(self):
        src = ("def log(path, line):\n"
               "    f = open(path, mode='ab')\n"
               "    g = open(path, 'x')\n")
        assert len(check_src(src, ["durability"], rel=self.REL)) == 2
        assert len(check_src(src, ["durability"], rel=self.REL_CKPT)) == 2

    def test_bare_rename_family_fires(self):
        src = ("import os, shutil\n"
               "def swap(a, b):\n"
               "    os.rename(a, b)\n"
               "    os.replace(a, b)\n"
               "    shutil.move(a, b)\n")
        fs = check_src(src, ["durability"], rel=self.REL)
        assert len(fs) == 3

    def test_path_write_text_fires(self):
        src = ("def mark(p):\n"
               "    p.write_text('done')\n")
        assert check_src(src, ["durability"], rel=self.REL)

    def test_serializer_to_path_fires_but_helper_callback_is_clean(self):
        bare = ("import numpy as np, json\n"
                "def dump(path, arrs, meta, f2):\n"
                "    np.savez(path, **arrs)\n"
                "    json.dump(meta, f2)\n")
        fs = check_src(bare, ["durability"], rel=self.REL)
        assert len(fs) == 2
        idiom = ("import numpy as np, json\n"
                 "from paddle_tpu.utils.durability import fsync_write\n"
                 "def dump(path, arrs, meta):\n"
                 "    fsync_write(path, lambda f: np.savez(f, **arrs))\n"
                 "    fsync_write(path + '.json',\n"
                 "                lambda f: f.write(json.dumps(meta)"
                 ".encode()))\n")
        assert check_src(idiom, ["durability"], rel=self.REL) == []

    def test_exec_store_is_confined(self):
        # the persistent executable cache (ISSUE 19) writes entries that
        # outlive processes: planted violations must fire there exactly
        # like in the resilience trees
        rel = "paddle_tpu/jit/exec_store.py"
        planted = ("import os, pickle\n"
                   "def put(path, payload):\n"
                   "    with open(path + '.tmp', 'wb') as f:\n"
                   "        pickle.dump(payload, f)\n"
                   "    os.rename(path + '.tmp', path)\n")
        fs = check_src(planted, ["durability"], rel=rel)
        assert len(fs) == 3   # bare open-for-write + serializer + rename
        idiom = ("from paddle_tpu.utils.durability import fsync_write\n"
                 "def put(path, payload):\n"
                 "    fsync_write(path, lambda f: f.write(payload))\n")
        assert check_src(idiom, ["durability"], rel=rel) == []
        # the shipped module itself must be clean under the rule
        shipped = open(os.path.join(PKG, "jit", "exec_store.py")).read()
        assert check_src(shipped, ["durability"], rel=rel) == []

    def test_reads_deletes_and_outside_paths_are_clean(self):
        src = ("import os, shutil, numpy as np\n"
               "def load(path):\n"
               "    with open(path) as f:\n"
               "        data = f.read()\n"
               "    z = np.load(path + '.npz')\n"
               "    os.unlink(path + '.tmp')\n"
               "    shutil.rmtree(path + '.old', ignore_errors=True)\n"
               "    return data, z\n")
        assert check_src(src, ["durability"], rel=self.REL) == []
        bare = ("def save(path, s):\n"
                "    open(path, 'w').write(s)\n")
        # the commit protocol's own home and ordinary code are exempt
        assert check_src(bare, ["durability"],
                         rel="paddle_tpu/utils/durability.py") == []
        assert check_src(bare, ["durability"],
                         rel="paddle_tpu/io/dataloader.py") == []


# ---------------------------------------------------------------------------
# timeouts (serving/fleet/: blocking calls must pass explicit timeouts)
# ---------------------------------------------------------------------------

class TestTimeoutsRule:
    REL = "paddle_tpu/serving/fleet/router.py"

    def test_bare_blocking_calls_fire(self):
        src = ("import queue\n"
               "def f(q, t, ev, lk, fut, proc):\n"
               "    a = q.get()\n"
               "    t.join()\n"
               "    ev.wait()\n"
               "    lk.acquire()\n"
               "    r = fut.result()\n"
               "    out = proc.communicate()\n")
        fs = check_src(src, ["timeouts"], rel=self.REL)
        assert len(fs) == 6
        assert all("timeout" in f.message for f in fs)

    def test_wait_for_needs_timeout_kwarg_despite_positional(self):
        # .wait_for's first positional is the PREDICATE, so the
        # zero-positional exemption must not apply to it
        src = ("def f(cv):\n"
               "    with cv:\n"
               "        cv.wait_for(lambda: done())\n")
        fs = check_src(src, ["timeouts"], rel=self.REL)
        assert len(fs) == 1 and "wait_for" in fs[0].message
        ok = ("def f(cv):\n"
              "    with cv:\n"
              "        cv.wait_for(lambda: done(), timeout=1.0)\n")
        assert check_src(ok, ["timeouts"], rel=self.REL) == []

    def test_positional_args_and_timeout_kwarg_are_clean(self):
        # dict.get(k) / ','.join(xs) / t.join(2.0) are the classic
        # false-positive shapes: a positional argument exempts the call
        src = ("def f(q, t, ev, d, xs, lk, proc):\n"
               "    a = q.get(timeout=1.0)\n"
               "    b = d.get('k')\n"
               "    s = ','.join(xs)\n"
               "    t.join(2.0)\n"
               "    ev.wait(timeout=0.5)\n"
               "    lk.acquire(timeout=1.0)\n"
               "    out = proc.communicate(timeout=10.0)\n")
        assert check_src(src, ["timeouts"], rel=self.REL) == []

    def test_outside_fleet_tree_is_exempt(self):
        src = ("def f(ev):\n"
               "    ev.wait()\n")
        assert check_src(src, ["timeouts"],
                         rel="paddle_tpu/serving/resilience/engine.py") == []
        assert check_src(src, ["timeouts"],
                         rel="paddle_tpu/models/serving.py") == []

    def test_suppression_with_justification_works(self):
        src = ("def f(ev):\n"
               "    ev.wait()  "
               "# graftcheck: disable=timeouts -- parent supervises\n")
        assert check_src(src, ["timeouts"], rel=self.REL) == []


# ---------------------------------------------------------------------------
# compat-shim (migrated from the PR-4 standalone lint)
# ---------------------------------------------------------------------------

class TestCompatShimRule:
    SAMPLES = [
        "import jax\njax.shard_map(lambda x: x)\n",
        "from jax.experimental.shard_map import shard_map\n",
        "import jax.experimental.shard_map as sm\n",
        "from jax.experimental import pallas as pl\n"
        "import jax\n"
        "params = jax.experimental.mosaic.CompilerParams()\n",
        "from jax.experimental.pallas import tpu as pltpu\n"
        "p = pltpu.TPUCompilerParams(dimension_semantics=())\n",
    ]

    @pytest.mark.parametrize("i", range(5))
    def test_planted_violations_fire(self, i):
        assert check_src(self.SAMPLES[i], ["compat-shim"]), \
            f"lint missed: {self.SAMPLES[i]!r}"

    def test_docstring_mentions_are_not_violations(self):
        src = ('"""Uses jax.shard_map via the shim; see '
               'CompilerParams docs."""\nX = 1\n')
        assert check_src(src, ["compat-shim"]) == []

    def test_jax_compat_itself_is_allowed(self):
        assert check_src(self.SAMPLES[0], ["compat-shim"],
                         rel="paddle_tpu/jax_compat.py") == []


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------

class TestTaxonomyRule:
    REASONS = ('MY_FALLBACK_REASONS = frozenset({"known reason"})\n',)

    def _check(self, body):
        return check_src(
            body, ["taxonomy"],
            extra_files=[("reasons.py", self.REASONS[0])])

    def test_member_literal_is_clean(self):
        assert self._check(
            'def f(self):\n    self._fallback("known reason")\n') == []

    def test_typo_fires(self):
        fs = self._check(
            'def f(self):\n    self._fallback("knwon reason")\n')
        assert len(fs) == 1 and "taxonomy fork" in fs[0].message

    def test_fstring_in_reason_position_fires(self):
        fs = self._check(
            'def f(self, e):\n    self._fallback(f"bad {e}")\n')
        assert len(fs) == 1 and "f-string" in fs[0].message

    def test_detail_argument_is_not_checked(self):
        assert self._check(
            'def f(self, e):\n'
            '    self._fallback("known reason", f"detail {e}")\n') == []

    def test_record_fallback_key_position(self):
        fs = self._check(
            'def f():\n    record_fallback("flash", "nope", "detail")\n')
        assert len(fs) == 1 and "'nope'" in fs[0].message

    def test_metric_name_fork_fires(self):
        fs = check_src(
            'import m\nc = m.registry().counter("dispatch.cuont")\n',
            ["taxonomy"],
            extra_files=[("metrics.py",
                          'METRIC_NAMES = frozenset({"dispatch.count"})\n')])
        assert len(fs) == 1 and "METRIC_NAMES" in fs[0].message

    def test_dead_metric_name_fires_on_its_definition_line(self):
        """A METRIC_NAMES entry nothing registers is a dead scrape
        series: flagged at the entry's own line, once the run carries
        registration sites in >=2 files besides the definer. Literal
        registrations and the `"prefix." + var` loop idiom both count
        as live, whatever the receiver is spelled as."""
        defs = ('METRIC_NAMES = frozenset({\n'
                '    "a.live",\n'
                '    "a.pfx.one",\n'
                '    "b.dead",\n'
                '})\n')
        regs = [("reg1.py", 'import m\nm.registry().counter("a.live")\n'),
                ("reg2.py", 'for _k in ("one",):\n'
                            '    reg.gauge("a.pfx." + _k)\n')]
        fs = check_src(defs, ["taxonomy"], rel="metrics.py",
                       extra_files=regs)
        assert len(fs) == 1
        assert "'b.dead'" in fs[0].message
        assert "dead taxonomy entry" in fs[0].message
        assert fs[0].line == 4

    def test_dead_check_stays_disarmed_on_scoped_runs(self):
        # one registering file besides the definer: a file-scoped run,
        # not evidence the rest of the tree stopped registering
        defs = 'METRIC_NAMES = frozenset({"b.dead"})\n'
        fs = check_src(defs, ["taxonomy"], rel="metrics.py",
                       extra_files=[("reg1.py",
                                     'import m\n'
                                     'm.registry().counter("b.other")\n')])
        assert fs == []

    INCIDENTS = ('INCIDENT_KINDS = frozenset({"serving.hang", '
                 '"fleet.failover"})\n')

    def _check_incident(self, body, extra=()):
        return check_src(
            body, ["taxonomy"],
            extra_files=[("incident.py", self.INCIDENTS), *extra])

    def test_incident_member_kind_is_clean(self):
        assert self._check_incident(
            'def f():\n    record_incident("serving.hang")\n') == []
        assert self._check_incident(
            'def f():\n    record_incident(kind="fleet.failover")\n') == []

    def test_incident_kind_typo_fires(self):
        fs = self._check_incident(
            'def f():\n    record_incident("serving.hagn")\n')
        assert len(fs) == 1
        assert "INCIDENT_KINDS" in fs[0].message
        assert "'serving.hagn'" in fs[0].message

    def test_incident_fstring_kind_fires(self):
        fs = self._check_incident(
            'def f(n):\n    record_incident(f"serving.{n}")\n')
        assert len(fs) == 1 and "f-string" in fs[0].message

    def test_incident_attrs_are_not_checked(self):
        assert self._check_incident(
            'def f(e):\n'
            '    record_incident("serving.hang", attrs={"e": f"x {e}"})\n'
        ) == []

    def test_dead_incident_kind_fires_on_its_definition_line(self):
        # "fleet.failover" defined but recorded nowhere; trigger sites
        # in >=2 other files arm the check (same rule as dead metrics)
        defs = ('INCIDENT_KINDS = frozenset({\n'
                '    "serving.hang",\n'
                '    "fleet.failover",\n'
                '})\n')
        sites = [("eng.py", 'record_incident("serving.hang")\n'),
                 ("trn.py", 'record_incident("serving.hang")\n')]
        fs = check_src(defs, ["taxonomy"], rel="incident.py",
                       extra_files=sites)
        assert len(fs) == 1
        assert "'fleet.failover'" in fs[0].message
        assert "dead incident class" in fs[0].message
        assert fs[0].line == 3

    def test_dead_incident_check_stays_disarmed_on_scoped_runs(self):
        defs = 'INCIDENT_KINDS = frozenset({"fleet.failover"})\n'
        fs = check_src(defs, ["taxonomy"], rel="incident.py",
                       extra_files=[("eng.py",
                                     'record_incident("serving.hang")\n')])
        assert fs == []

    def test_frozen_sets_actually_exist_in_package(self):
        # the rule is vacuous without the runtime sets: pin them
        from paddle_tpu.jit.step_capture import FALLBACK_REASONS
        from paddle_tpu.observability.incident import INCIDENT_KINDS
        from paddle_tpu.observability.metrics import METRIC_NAMES
        from paddle_tpu.ops.kernels.pallas.tp_attention import \
            TP_FALLBACK_REASONS
        assert "trace failed" in FALLBACK_REASONS
        assert "flags_off" in TP_FALLBACK_REASONS
        assert "step_capture.static_screened" in METRIC_NAMES
        assert "serving.hang" in INCIDENT_KINDS
        assert "incident.recorded" in METRIC_NAMES

    def test_runtime_validation_rejects_unknown_reason(self):
        import paddle_tpu as paddle
        from paddle_tpu.ops.kernels.pallas import tp_attention as tpa

        def step(x):
            return x

        cap = paddle.jit_step(step)
        with pytest.raises(ValueError, match="unregistered"):
            cap._fallback("no such reason")
        with pytest.raises(ValueError, match="unregistered"):
            tpa.record_fallback("flash", "no_such_key", "detail")

    def test_runtime_validation_rejects_unknown_incident_kind(self):
        from paddle_tpu.observability import incident
        with pytest.raises(ValueError, match="INCIDENT_KINDS"):
            incident.IncidentRecorder().record("no.such.kind")

    def test_serving_quant_spec_taxonomies_exist_in_package(self):
        # the int8-KV fallback reason and the int8-KV / speculative-decode
        # serving metrics are frozen taxonomy, same as the TP reasons. The
        # set holds the one reason the tree can still record: the dense
        # generate() loop has no quantized layout
        from paddle_tpu.observability.metrics import METRIC_NAMES
        from paddle_tpu.ops.kernels import serving
        assert serving.KV_QUANT_FALLBACK_REASONS == {"kv_int8_dense_cache"}
        for name in ("serving.kv.bytes_per_token",
                     "serving.kv.dequant_blocks", "serving.kv.fallback",
                     "serving.spec.proposed", "serving.spec.accepted",
                     "serving.spec.rejected", "serving.spec.verify_rows"):
            assert name in METRIC_NAMES, name

    def test_planted_kv_quant_reason_typo_fires(self):
        reasons = ('KV_QUANT_FALLBACK_REASONS = '
                   'frozenset({"kv_int8_gang_pallas"})\n')
        fs = check_src(
            'def f():\n'
            '    record_fallback("paged", "kv_int8_gang_palas", "d")\n',
            ["taxonomy"], extra_files=[("s.py", reasons)])
        assert len(fs) == 1 and "taxonomy fork" in fs[0].message

    def test_planted_spec_reason_fstring_fires(self):
        reasons = ('SPEC_FALLBACK_REASONS = '
                   'frozenset({"spec_gang_engine"})\n')
        fs = check_src(
            'def f(e):\n'
            '    record_fallback("spec", f"spec_{e}", "d")\n',
            ["taxonomy"], extra_files=[("s.py", reasons)])
        assert len(fs) == 1 and "f-string" in fs[0].message

    def test_planted_spec_metric_typo_fires(self):
        fs = check_src(
            'import m\n'
            'c = m.registry().counter("serving.spec.acccepted")\n',
            ["taxonomy"],
            extra_files=[("metrics.py",
                          'METRIC_NAMES = frozenset({'
                          '"serving.spec.accepted"})\n')])
        assert len(fs) == 1 and "METRIC_NAMES" in fs[0].message

    def test_runtime_validation_rejects_unknown_serving_fallback(self):
        from paddle_tpu.ops.kernels import serving as ksrv
        with pytest.raises(ValueError, match="unregistered"):
            ksrv.record_fallback("kv", "no_such_key", "detail")


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpansRule:
    SPANS = ('SPAN_NAMES = frozenset({"fleet.submit", '
             '"serving.first_token"})\n',)

    def _check(self, body):
        return check_src(
            body, ["spans"],
            extra_files=[("tracing.py", self.SPANS[0])])

    def test_member_literal_is_clean(self):
        assert self._check(
            'import t\nwith t.span("fleet.submit"):\n    pass\n') == []

    def test_typo_fires(self):
        fs = self._check(
            'import t\nwith t.span("fleet.submt"):\n    pass\n')
        assert len(fs) == 1 and "taxonomy fork" in fs[0].message

    def test_every_callee_is_covered(self):
        for call in ('t.start_span("nope.x")',
                     't.record_span("nope.x", 0, 1)',
                     't.instant("nope.x")',
                     'sp.event("nope.x")'):
            fs = self._check(f'import t\n{call}\n')
            assert len(fs) == 1, call

    def test_fstring_in_name_position_fires(self):
        fs = self._check(
            'import t\ndef f(g):\n    t.instant(f"fleet.{g}")\n')
        assert len(fs) == 1 and "f-string" in fs[0].message

    def test_name_keyword_is_checked(self):
        fs = self._check('import t\nt.instant(name="nope.x")\n')
        assert len(fs) == 1

    def test_attrs_are_not_checked(self):
        assert self._check(
            'import t\ndef f(e):\n'
            '    t.instant("serving.first_token", '
            'attrs={"why": f"bad {e}"})\n') == []

    def test_unrelated_span_callables_checked_by_terminal_name_only(self):
        # threading.Event() etc. don't collide: the terminal names are
        # case-sensitive and the argument must be a string literal
        assert self._check(
            'import threading\nev = threading.Event()\nev.set()\n') == []

    def test_suppression_with_justification(self):
        assert self._check(
            'import t\nt.instant("nope.x")'
            '  # graftcheck: disable=spans -- exercising the validator\n'
        ) == []

    def test_frozen_set_actually_exists_in_package(self):
        from paddle_tpu.observability.tracing import SPAN_NAMES
        for name in ("fleet.submit", "serving.admit",
                     "serving.journal_fsync", "serving.first_token",
                     "step_capture.replay", "optimizer.update",
                     "checkpoint.commit", "jit.compile"):
            assert name in SPAN_NAMES, name


# ---------------------------------------------------------------------------
# hygiene: silent-except + test-flag-restore
# ---------------------------------------------------------------------------

class TestSilentExceptRule:
    def test_uncommented_swallow_fires(self):
        src = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except Exception:\n"
               "        pass\n")
        fs = check_src(src, ["silent-except"])
        assert len(fs) == 1 and "swallows Exception" in fs[0].message

    def test_bare_except_fires(self):
        src = "def f():\n    try:\n        g()\n    except:\n        pass\n"
        assert len(check_src(src, ["silent-except"])) == 1

    def test_justification_comment_accepted(self):
        src = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except Exception:\n"
               "        pass  # teardown path: worker may be gone\n")
        assert check_src(src, ["silent-except"]) == []

    def test_comment_on_own_line_before_pass_accepted(self):
        src = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except Exception:\n"
               "        # teardown path: worker may be gone\n"
               "        pass\n")
        assert check_src(src, ["silent-except"]) == []

    def test_narrow_except_tuple_is_deliberate(self):
        src = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except (OSError, ConnectionError):\n"
               "        pass\n")
        assert check_src(src, ["silent-except"]) == []

    def test_handler_with_logic_is_clean(self):
        src = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except Exception:\n"
               "        x = 1\n")
        assert check_src(src, ["silent-except"]) == []


class TestTestFlagRestoreRule:
    def test_unrestored_mutation_fires(self):
        src = ("import paddle_tpu as paddle\n"
               "def test_x():\n"
               "    paddle.set_flags({'FLAGS_benchmark': True})\n"
               "    assert True\n")
        fs = check_src(src, ["test-flag-restore"])
        assert len(fs) == 1 and "benchmark" in fs[0].message

    def test_try_finally_restore_is_clean(self):
        src = ("import paddle_tpu as paddle\n"
               "def test_x():\n"
               "    paddle.set_flags({'FLAGS_benchmark': True})\n"
               "    try:\n"
               "        assert True\n"
               "    finally:\n"
               "        paddle.set_flags({'FLAGS_benchmark': False})\n")
        assert check_src(src, ["test-flag-restore"]) == []

    def test_snapshot_restore_in_finally_is_clean(self):
        src = ("import paddle_tpu as paddle\n"
               "def test_x():\n"
               "    prev = paddle.get_flags('FLAGS_benchmark')\n"
               "    paddle.set_flags({'FLAGS_benchmark': True})\n"
               "    try:\n"
               "        assert True\n"
               "    finally:\n"
               "        paddle.set_flags(prev)\n")
        assert check_src(src, ["test-flag-restore"]) == []

    def test_autouse_fixture_guards_module(self):
        src = ("import pytest\nimport paddle_tpu as paddle\n"
               "@pytest.fixture(autouse=True)\n"
               "def _guard():\n"
               "    paddle.set_flags({'FLAGS_step_capture': True})\n"
               "    yield\n"
               "    paddle.set_flags({'FLAGS_step_capture': True})\n"
               "def helper(on):\n"
               "    paddle.set_flags({'FLAGS_step_capture': on})\n")
        assert check_src(src, ["test-flag-restore"]) == []

    def test_fixture_guards_only_its_flags(self):
        src = ("import pytest\nimport paddle_tpu as paddle\n"
               "@pytest.fixture(autouse=True)\n"
               "def _guard():\n"
               "    yield\n"
               "    paddle.set_flags({'FLAGS_step_capture': True})\n"
               "def test_y():\n"
               "    paddle.set_flags({'FLAGS_metrics': False})\n")
        fs = check_src(src, ["test-flag-restore"])
        assert len(fs) == 1 and "metrics" in fs[0].message

    def test_jax_config_update_without_restore_fires(self):
        src = ("import jax\n"
               "def test_z():\n"
               "    jax.config.update('jax_enable_x64', True)\n")
        fs = check_src(src, ["test-flag-restore"])
        assert len(fs) == 1 and "jax_enable_x64" in fs[0].message


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

class TestSuppressions:
    SRC = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    except Exception:  "
           "# graftcheck: disable=silent-except -- best-effort probe\n"
           "        pass\n")

    def test_trailing_suppression_with_justification(self):
        assert check_src(self.SRC, ["silent-except"]) == []

    def test_previous_line_suppression(self):
        src = ("import jax\n"
               "def f(s):\n"
               "    jfn = jax.jit(g, donate_argnums=(0,))\n"
               "    out = jfn(s)\n"
               "    # graftcheck: disable=donation-safety -- checked above\n"
               "    return s\n")
        assert check_src(src, ["donation-safety"]) == []

    def test_wrong_rule_id_does_not_suppress(self):
        # (on a rule without comment-justification semantics, since any
        # comment — including a mismatched disable — pacifies
        # silent-except by design)
        src = ("import jax\n"
               "def f(s):\n"
               "    jfn = jax.jit(g, donate_argnums=(0,))\n"
               "    out = jfn(s)\n"
               "    return s  # graftcheck: disable=trace-purity -- nope\n")
        fs = check_src(src, ["donation-safety"])
        assert len(fs) == 1

    def test_bare_suppression_is_itself_a_finding(self):
        src = self.SRC.replace(" -- best-effort probe", "")
        fs = [f for f in run_files([SourceFile("s.py", src, "s.py")],
                                   rule_ids=["silent-except"])]
        assert any(f.rule == "suppression-justification" for f in fs)
        assert not any(f.rule == "silent-except" for f in fs)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def _planted(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("def f():\n    try:\n        g()\n"
                     "    except Exception:\n        pass\n")
        return str(p)

    def test_exit_zero_on_clean(self, tmp_path, capsys):
        p = tmp_path / "ok.py"
        p.write_text("X = 1\n")
        assert cli_main([str(p)]) == 0
        assert "0 findings" in capsys.readouterr().out

    def test_exit_one_on_findings_text(self, tmp_path, capsys):
        rc = cli_main([self._planted(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "[silent-except]" in out and "bad.py:4" in out

    def test_json_format(self, tmp_path, capsys):
        rc = cli_main(["--format", "json", self._planted(tmp_path)])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["count"] == 1
        assert doc["findings"][0]["rule"] == "silent-except"
        assert doc["findings"][0]["line"] == 4

    def test_exit_two_on_usage_errors(self, tmp_path, capsys):
        assert cli_main([]) == 2
        assert cli_main(["--rules", "no-such-rule", str(tmp_path)]) == 2
        assert cli_main([str(tmp_path / "missing_dir")]) == 2
        capsys.readouterr()

    def test_rules_filter(self, tmp_path, capsys):
        rc = cli_main(["--rules", "trace-purity", self._planted(tmp_path)])
        assert rc == 0          # silent-except excluded by the filter
        capsys.readouterr()

    def test_parse_error_is_a_finding(self, tmp_path, capsys):
        p = tmp_path / "broken.py"
        p.write_text("def f(:\n")
        assert cli_main([str(p)]) == 1
        assert "parse-error" in capsys.readouterr().out

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("capture-safety", "donation-safety", "trace-purity",
                    "compat-shim", "taxonomy", "spans", "silent-except",
                    "test-flag-restore", "durability", "timeouts"):
            assert rid in out

    @pytest.mark.heavy
    def test_console_module_entry(self, tmp_path):
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--list-rules"],
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0
        assert "donation-safety" in out.stdout


# ---------------------------------------------------------------------------
# tier-1 self-run: the framework's own sources must be clean
# ---------------------------------------------------------------------------

class TestSelfRun:
    def test_paddle_tpu_is_clean_under_src_profile(self):
        t0 = time.perf_counter()
        findings = run_paths([PKG], profile="src", root=REPO)
        dt = time.perf_counter() - t0
        assert findings == [], "unsuppressed graftcheck findings:\n" + \
            "\n".join(f.format() for f in findings)
        assert dt < 10.0, f"analyzer over paddle_tpu/ took {dt:.1f}s " \
                          f"(budget 10s — keep rules single-pass)"

    def test_tests_are_clean_under_test_profile(self):
        findings = run_paths([TESTS], profile="test", root=REPO)
        assert findings == [], "unsuppressed graftcheck findings:\n" + \
            "\n".join(f.format() for f in findings)


pytestmark = pytest.mark.smoke
