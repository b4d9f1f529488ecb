"""focuslint analyzer tests: one fixture violation per rule family
(plus a clean file), asserted through the JSON report, and regression
coverage for suppressions and the real ClusterStore.attach exemption."""
import json
import os

import pytest

from repro.analysis.runner import run_analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, files, **kw):
    """Write {relpath: source} under tmp_path, lint, return parsed JSON."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    report = run_analysis([str(tmp_path)], **kw)
    return json.loads(report.to_json(show_suppressed=True))


def rules_of(doc, fname=None):
    return sorted({f["rule"] for f in doc["findings"]
                   if fname is None or f["path"].endswith(fname)})


# -- rule family 1: host-sync & retrace hazards --------------------------------

def test_host_sync_inside_traced_function(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def traced(x):\n"
        "    return np.asarray(x) + 1\n")})
    assert rules_of(doc) == ["host-sync"]
    assert doc["findings"][0]["line"] == 5


def test_host_sync_in_dispatcher_on_device_value(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def hot(x):\n"
        "    y = f(x)\n"
        "    return int(y)\n")})
    assert rules_of(doc) == ["host-sync"]
    assert doc["findings"][0]["line"] == 7


def test_host_coercion_of_host_value_not_flagged(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def hot(x, meta):\n"
        "    y = f(x)\n"
        "    n = int(meta['count'])\n"       # host dict: no finding
        "    return y, n\n")})
    assert doc["findings"] == []


def test_retrace_hazard_static_arg(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "def g(k, x):\n"
        "    return x[:k]\n"
        "gj = jax.jit(g, static_argnums=(0,))\n"
        "def caller(x):\n"
        "    return gj(int(x.sum()), x)\n")})
    assert "retrace-hazard" in rules_of(doc)


# -- rule family 2: donation-after-use -----------------------------------------

def test_donated_read_after_call(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "def step(a, b):\n"
        "    return a + b\n"
        "stepj = jax.jit(step, donate_argnums=(0,))\n"
        "def run(a, b):\n"
        "    out = stepj(a, b)\n"
        "    return a.sum() + out\n")})
    assert rules_of(doc) == ["donated-read"]
    assert doc["findings"][0]["line"] == 7


def test_donated_arg_reassigned_is_clean(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "def step(a, b):\n"
        "    return a + b\n"
        "stepj = jax.jit(step, donate_argnums=(0,))\n"
        "def run(a, b):\n"
        "    a = stepj(a, b)\n"               # rebinds the donated name
        "    return a.sum()\n")})
    assert doc["findings"] == []


# -- rule family 3: kernel contract --------------------------------------------

_KERNEL = (
    "from jax.experimental import pallas as pl\n"
    "def _body(x_ref, o_ref):\n"
    "    o_ref[...] = x_ref[...]\n"
    "def mykern(x):\n"
    "    return pl.pallas_call(_body, out_shape=x)(x)\n")


def test_kernel_without_oracle_wrapper_or_test(tmp_path):
    doc = lint(tmp_path, {"kernels/mykern.py": _KERNEL})
    assert rules_of(doc) == ["kernel-oracle", "kernel-test",
                             "kernel-wrapper"]


def test_pallas_call_outside_kernels_is_error(tmp_path):
    doc = lint(tmp_path, {"other.py": _KERNEL})
    assert "pallas-outside-kernels" in rules_of(doc)


# -- rule family 4: cache-version ----------------------------------------------

_STORE = (
    "class Store:\n"
    "    def bad(self, rows, vals):\n"
    "        self.centroids[rows] = vals\n"
    "    def good(self, rows, vals):\n"
    "        self.centroids[rows] = vals\n"
    "        self.versions[rows] += 1\n")


def test_cache_version_unbumped_mutation(tmp_path):
    doc = lint(tmp_path, {"store.py": _STORE})
    assert rules_of(doc) == ["cache-version"]
    assert doc["findings"][0]["line"] == 3          # bad(), not good()


# -- clean file ----------------------------------------------------------------

def test_clean_file_has_no_findings(tmp_path):
    doc = lint(tmp_path, {"clean.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return jnp.tanh(x)\n"
        "def host_only(a):\n"
        "    return np.asarray(a) + 1\n"     # no device value involved
        "def hot(x):\n"
        "    return f(x)\n")})
    assert doc["findings"] == []
    assert doc["n_findings"] == 0


# -- suppressions --------------------------------------------------------------

def test_suppression_with_justification(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def hot(x):\n"
        "    y = f(x)\n"
        "    # focuslint: disable=host-sync -- test boundary\n"
        "    return int(y)\n")})
    assert doc["findings"] == []
    assert doc["n_suppressed"] == 1
    assert doc["suppressed"][0]["justification"] == "test boundary"


def test_bare_suppression_is_itself_a_finding(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def hot(x):\n"
        "    y = f(x)\n"
        "    return int(y)  # focuslint: disable=host-sync\n")})
    assert rules_of(doc) == ["bare-suppression"]
    assert doc["n_suppressed"] == 1


def test_function_scope_suppression_on_def_line(tmp_path):
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def hot(x):  # focuslint: disable=host-sync -- whole fn\n"
        "    y = f(x)\n"
        "    z = int(y)\n"
        "    return float(y) + z\n")})
    assert doc["findings"] == []
    assert doc["n_suppressed"] == 2


def test_select_filters_rules(tmp_path):
    doc = lint(tmp_path, {"store.py": _STORE, "kern.py": _KERNEL},
               select=["cache-version"])
    assert rules_of(doc) == ["cache-version"]


def test_sharded_fetch_without_suppression_is_flagged(tmp_path):
    """The sharded fold boundary (DESIGN.md §13): a dispatcher doing a
    device_get of stacked outputs is flagged unless suppressed — an extra
    sync sneaking into the sharded fetch path cannot land silently — and
    the message names the one sanctioned boundary."""
    doc = lint(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def megastep(x):\n"
        "    return x * 2, x > 0\n"
        "def pump_one(x):\n"
        "    j, matched = megastep(x)\n"
        "    j_h, m_h = jax.device_get((j, matched))\n"
        "    return j_h, m_h\n")})
    assert rules_of(doc) == ["host-sync"]
    assert "fold boundary" in doc["findings"][0]["message"]
    assert doc["findings"][0]["line"] == 7


# -- the repo itself -----------------------------------------------------------

def test_sharded_pipeline_sync_budget_is_pinned():
    """Regression fixture (ISSUE 9): core/pipeline.py carries exactly the
    designed set of sanctioned host syncs. A new ``device_get`` in the
    megastep path must either fail the CI lint gate or consciously bump
    this pin with a justified suppression."""
    path = os.path.join(REPO_ROOT, "src", "repro", "core", "pipeline.py")
    report = run_analysis([path])
    doc = json.loads(report.to_json(show_suppressed=True))
    assert not [f for f in doc["findings"] if f["rule"] == "host-sync"]
    syncs = [f for f in doc["suppressed"] if f["rule"] == "host-sync"]
    # staged boundary x2 + 3 megastep ((j,matched) stack fetch, fold-rows
    # fetch, bound-gated (S,) n); the evict/reset slot pulls sit in
    # non-dispatcher functions, outside the hot path this rule guards
    assert len(syncs) == 5, sorted(f["line"] for f in syncs)
    boundary = [f for f in syncs if "fold boundary" in f["justification"]
                or "designed" in f["justification"]]
    assert len(boundary) >= 2      # both fold-boundary fetches named


def test_repo_archive_rank_sync_budget_is_pinned():
    """Regression fixture (ISSUE 10): the lazy v4 shard path in
    core/archive.py carries exactly ONE sanctioned host sync — the
    once-per-shard fetch of the dequant_topk rank ids, cached for the
    shard's resident lifetime. A second device fetch on the archive rank
    path must fail the lint gate or consciously bump this pin."""
    src = os.path.join(REPO_ROOT, "src", "repro")
    # the kernels package must be in the analysis set: _rank_ids is hot
    # only because it reaches the jitted ops.dequant_topk wrapper
    report = run_analysis([os.path.join(src, "core", "archive.py"),
                           os.path.join(src, "kernels")])
    doc = json.loads(report.to_json(show_suppressed=True))
    assert not [f for f in doc["findings"] if f["rule"] == "host-sync"]
    syncs = [f for f in doc["suppressed"] if f["rule"] == "host-sync"]
    assert len(syncs) == 1, sorted(f["line"] for f in syncs)
    assert "once-per-shard" in syncs[0]["justification"]


def test_repo_attach_exemption_is_suppressed():
    """ClusterStore.attach's count-only mutation is the one sanctioned
    cache-version exemption — suppressed with a recorded rationale."""
    path = os.path.join(REPO_ROOT, "src", "repro", "core", "index.py")
    report = run_analysis([path])
    doc = json.loads(report.to_json(show_suppressed=True))
    attach = [f for f in doc["suppressed"]
              if f["rule"] == "cache-version"]
    assert len(attach) == 1
    assert "intentional exemption" in attach[0]["justification"]
    assert not [f for f in doc["findings"]
                if f["rule"] == "cache-version"]


@pytest.mark.slow
def test_repo_is_clean():
    """The CI gate invariant: the whole tree lints clean."""
    paths = [os.path.join(REPO_ROOT, d)
             for d in ("src", "benchmarks", "tests")]
    report = run_analysis(paths)
    assert report.active == [], report.to_text()
