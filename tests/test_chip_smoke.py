"""``chip_smoke.py`` rehearsed without a chip.

The script's own ``--rehearse`` runs every phase at a tiny size with its
children held to the CPU backend (four virtual devices for ``--chips
4``); these tests hold it to the shape of its contract — one JSON line a
phase, the device line last and nothing else in it — and to the two ways
it must fail: on a CPU without ``--rehearse``, and alone in a directory.
What only the chip can say (the kernel in the compiled text, HBM, bf16
distances) is the chip run's to say; tests/test_tpu_compile.py covers
what the chip's compiler says.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_changes, cwd=REPO, script=SCRIPT):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    proc = subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=1500,
    )
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


def _phases(lines):
    docs = [json.loads(l) for l in lines]  # every stdout line is JSON
    return {d["phase"]: d for d in docs if "phase" in d}, docs[-1]


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """The one-chip rehearsal, twice over one placed compile cache."""
    cache = tmp_path_factory.mktemp("placed-cache")
    env = {
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        # conftest switches the cache off for the suite's children; these
        # two runs are the ones that test it
        "JAX_ENABLE_COMPILATION_CACHE": None,
    }
    default_before = _default_cache_listing()
    runs = []
    for _ in range(2):
        proc, lines = _run(["--rehearse"], env)
        assert proc.returncode == 0, proc.stderr[-3000:]
        runs.append(lines)
    return cache, runs, default_before


def _default_cache_listing():
    """<checkout>/.jax_cache as it stands (None where it does not exist:
    a developer's own chip_smoke run may have made it)."""
    try:
        return sorted(os.listdir(os.path.join(REPO, ".jax_cache")))
    except FileNotFoundError:
        return None


def test_rehearsal_prints_a_line_a_phase_and_the_device_line_last(rehearsals):
    _cache, (lines, _warm), _ = rehearsals
    phases, last = _phases(lines)
    assert list(phases) == ["widths", "train", "serve", "cache"]
    assert all(d["ok"] is True for d in phases.values())
    # the contract's last line: these keys and no others
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    widths, train, serve = phases["widths"], phases["train"], phases["serve"]
    assert widths["device"] == last["device"] == serve["device"]
    assert widths["flash"]["fwd_rel_err"] <= widths["flash"]["fwd_tol"]
    assert widths["flash"]["bwd_rel_err"] <= widths["flash"]["bwd_tol"]
    eng = widths["engine"]
    assert eng["kv_layout"] == "paged" and eng["requests"] == 6
    assert 0 < eng["prefill_floors"] <= eng["floors_allowed"]
    assert eng["decode_floors"] <= eng["floors_allowed"]
    # the rule is shown to bite: the same requests decoded over a
    # corrupted KV pool are refused by it (the phase fails otherwise)
    control = eng["negative_control"]
    assert control["decode_floors"] > eng["floors_allowed"]
    assert control["streams_changed"] == 3
    assert train["steps"] == 6 and train["syncs"] == 3
    assert train["last_loss"] < train["first_loss"]
    # every phase names the device it ran on itself: the trainer in its
    # cost record, the server on /healthz
    assert train["device_kind"] == "cpu" and train["flops_per_token_xla"] > 0
    assert any(k.startswith("train_round:") for k in train["compile_s_by_program"])
    # off the chip the served streams are bit-identical to solo generate()
    assert serve["streams_identical_to_generate"] == "4/4"
    assert serve["first_differences"] == [] and serve["requests"] == 4
    assert serve["served_floors"] <= serve["floors_allowed"]


def test_compile_cache_is_where_it_was_placed_and_second_run_adds_nothing(rehearsals):
    cache, (cold, warm), default_before = rehearsals
    cold_line, warm_line = _phases(cold)[0]["cache"], _phases(warm)[0]["cache"]
    assert cold_line["dir"] == str(cache) == warm_line["dir"]
    assert cold_line["entries_before"] == 0 < cold_line["entries_after"]
    assert warm_line["entries_before"] == cold_line["entries_after"]
    assert warm_line["entries_after"] == warm_line["entries_before"]
    assert len(os.listdir(cache)) >= cold_line["entries_after"]
    # and nowhere else: neither run touched the default directory
    assert _default_cache_listing() == default_before


def test_chips4_rehearsal_runs_only_the_multichip_path():
    proc, lines = _run(["--chips", "4", "--rehearse"], {})
    assert proc.returncode == 0, proc.stderr[-3000:]
    phases, last = _phases(lines)
    assert list(phases) == ["multichip", "cache"]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    layouts = {(l["layout"], l["attention"]): l for l in phases["multichip"]["layouts"]}
    assert set(layouts) == {
        (name, attention) for name in ("diloco4", "diloco2_fsdp2")
        for attention in ("dense", "flash")
    }
    for (name, attention), lay in layouts.items():
        assert lay["wq_shard_devices"] == [0, 1, 2, 3]
        assert lay["loss_max_abs_diff_vs_one_chip"] <= phases["multichip"]["loss_tol"]
        assert (lay["snapshot_diff_vs_one_chip_over_distance_moved"]
                <= phases["multichip"]["snapshot_tol"])
        # the entry point runs the upstream job's (dense) attention
        assert ("cli" in lay) == (attention == "dense")
        # [W, L, d, heads]: a worker a shard; fsdp halves the input dimension
        assert lay["wq_shard_shape"] == [1, 6, 128 if name == "diloco4" else 64, 128]
    for name in ("diloco4", "diloco2_fsdp2"):
        cli = layouts[name, "dense"]["cli"]
        assert cli["steps"] == 4 and cli["syncs"] == 2


def _chip_output_listing():
    """What a chip run left under <checkout>/chiprun_out/chip_smoke, with
    sizes and times (None where there is none)."""
    out = os.path.join(REPO, "chiprun_out", "chip_smoke")
    try:
        return sorted(
            (n, os.path.getsize(os.path.join(out, n)), os.path.getmtime(os.path.join(out, n)))
            for n in os.listdir(out)
        )
    except FileNotFoundError:
        return None


@pytest.mark.parametrize("chips", ["1", "4"])
def test_cpu_run_without_rehearse_fails_and_prints_no_result(chips):
    before = _chip_output_listing()
    proc, lines = _run(["--chips", chips], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not 'tpu'" in proc.stderr or "wanted 4 device" in proc.stderr
    # a refused run writes nothing into the checkout, least of all over
    # the logs a chip run brought back
    assert _chip_output_listing() == before


def test_serve_phase_refuses_a_server_that_came_up_on_the_cpu(tmp_path, monkeypatch):
    """With no platform named, JAX falls back to the CPU quietly when it
    cannot take the chip, and the ``serve`` CLI has no guard of its own:
    the phase must read the device off the server's own /healthz and fail,
    whatever the other children ran on."""
    sys.path.insert(0, REPO)
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "_LOG_DIR", str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    sizes = chip_smoke.SIZES["rehearse"]
    _doc, ckpt = chip_smoke.phase_train(str(tmp_path), sizes, env, True, lambda: 600.0)
    try:
        with pytest.raises(chip_smoke.PhaseFailed, match="not on one tpu device"):
            # as a chip run would call it: rehearse=False
            chip_smoke.phase_serve(str(tmp_path), ckpt, sizes, env, False, lambda: 600.0)
    finally:
        chip_smoke._stop_all()


def test_script_alone_in_a_directory_fails(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path)
    proc, lines = _run([], {}, cwd=str(tmp_path), script=alone)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_parents_never_initialize_a_backend(tmp_path):
    """A chip belongs to one process: what starts children that need it
    (chip_smoke's parent, ``supervise``, the fleet's subprocess provider)
    must itself stay off the backend."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke, nanodiloco_tpu.cli\n"
        "from nanodiloco_tpu.serve.client import http_get\n"
        "from nanodiloco_tpu.resilience import supervisor\n"
        "from nanodiloco_tpu.fleet.autoscaler import ProcessReplicaProvider\n"
        "supervisor.latest_checkpoint_step(%r)\n"
        "ProcessReplicaProvider('serve --port {port}')\n"
        "chip_smoke.cache_entries(); chip_smoke.free_port()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    ) % (REPO, str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_bench_without_a_tpu_or_a_cpu_request_fails():
    """A measurement entry point that finds no accelerator fails; it does
    not print a CPU number under ``tokens_per_sec_per_chip``. (With
    JAX_PLATFORMS unset JAX looks for the TPU, finds none here and falls
    back to the CPU with a warning — the case utils.require_accelerator
    refuses.)"""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(BENCH_MID="0", TPU_LOG_DIR="disabled")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    assert "tokens_per_sec_per_chip" not in proc.stdout
    assert "JAX found no accelerator" in proc.stderr
