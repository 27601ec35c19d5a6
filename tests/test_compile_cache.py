"""Where the compile cache lives, and the CPU key under the test mesh.

The CPU key's job is: two hosts whose XLA:CPU codegen differs must get
different directories.  The /proc/cpuinfo proxy was seen to collide
(identical kernel-reported flags, different LLVM preference features —
the ``cpu_aot_loader.cc`` mismatch tail in MULTICHIP_r04), so the key is
a fingerprint of a serialized probe executable.  These tests pin the
key's inputs and sensitivity, and the placement rule around it.
"""

from __future__ import annotations

from mx_rcnn_tpu.utils import compile_cache


class TestLlvmTargetFeatures:
    def test_probe_contract_on_cpu_backend(self):
        # The suite runs with jax pinned to the fake-CPU backend
        # (conftest), which is the condition of every CPU caller.  The
        # probe returns a real ±feature run or (jaxlib 0.9.0: run not
        # embedded) a whole-blob hash.
        feats = compile_cache.llvm_target_features()
        if feats.startswith("blob:"):
            assert len(feats) == len("blob:") + 40  # sha1 hex
        else:
            toks = feats.split(",")
            assert len(toks) > 8
            assert all(t[0] in "+-" for t in toks)

    def test_probe_is_deterministic(self):
        assert (
            compile_cache.llvm_target_features()
            == compile_cache.llvm_target_features()
        )

    def test_fingerprint_keys_on_feature_string(self, monkeypatch):
        # The exact r3/r4 failure mode: same cpuinfo, one preference flag
        # different.  The fingerprint MUST move.  Synthetic strings so
        # the test holds on hosts where the real probe degrades.
        real = "+64bit,+avx,+avx2,+bmi,+bmi2,+cmov,+cx16,+fma,+sse4.2"
        monkeypatch.setattr(
            compile_cache, "llvm_target_features", lambda: real
        )
        base = compile_cache.cpu_fingerprint()
        flipped = real + ",+prefer-no-scatter"
        monkeypatch.setattr(
            compile_cache, "llvm_target_features", lambda: flipped
        )
        assert compile_cache.cpu_fingerprint() != base

    def test_fingerprint_survives_probe_failure(self, monkeypatch):
        # No-probe hosts degrade to the cpuinfo/uname key, distinctly
        # from any real feature string ("?" sentinel).
        monkeypatch.setattr(
            compile_cache, "llvm_target_features",
            lambda: "+64bit,+avx,+avx2,+fma",
        )
        base = compile_cache.cpu_fingerprint()
        monkeypatch.setattr(
            compile_cache, "llvm_target_features", lambda: None
        )
        fp = compile_cache.cpu_fingerprint()
        assert len(fp) == 8
        assert fp != base

    def test_fingerprint_stable_across_calls(self):
        assert compile_cache.cpu_fingerprint() == compile_cache.cpu_fingerprint()


class TestBlobFallback:
    def test_feature_run_preferred_when_present(self):
        run = b"+64bit,+avx,+avx2,+bmi,+bmi2,+cmov,+cx16,+f16c,+fma,+sse4.2"
        blob = b"junk\x00" + run + b"\x00MORE"
        assert compile_cache._features_from_blob(blob) == run.decode()

    def test_runless_blobs_hash_whole_blob(self):
        # jaxlib 0.9.0's serialization carries no recognizable feature
        # run; the key must then fingerprint the codegen'd bytes
        # themselves, NOT collapse to the collision-prone "?" sentinel.
        a = compile_cache._features_from_blob(b"\x00machine code A\x7f")
        b = compile_cache._features_from_blob(b"\x00machine code B\x7f")
        assert a.startswith("blob:") and b.startswith("blob:")
        assert a != b  # different codegen -> different key material

    def test_runless_probe_still_moves_fingerprint(self, monkeypatch):
        base = compile_cache.cpu_fingerprint()
        monkeypatch.setattr(
            compile_cache, "llvm_target_features",
            lambda: compile_cache._features_from_blob(b"other host bytes"),
        )
        assert compile_cache.cpu_fingerprint() != base


class TestConfigureCache:
    """The one rule (utils/compile_cache.py): a directory placed from
    outside is used exactly as given; otherwise two fixed paths.  Checked
    in fresh processes — the suite's own process was configured by
    conftest, and the rule is about what a process does at start-up."""

    @staticmethod
    def _run(tmp_path, env_dir):
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = compile_cache.REPO_ROOT
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        code = (
            "import jax, json;"
            "from mx_rcnn_tpu.utils import compile_cache as c;"
            "before = jax.config.jax_compilation_cache_dir;"
            "d = c.configure_cache();"
            "jax.jit(lambda x: x + 1)(1.0);"  # a compile, for good measure
            "print(json.dumps([before, d, "
            "jax.config.jax_compilation_cache_dir, c.cpu_fingerprint()]))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=tmp_path,
            capture_output=True, text=True, timeout=300, check=True,
        ).stdout
        import json

        return json.loads(out.strip().splitlines()[-1])

    def test_a_directory_placed_from_outside_is_left_alone(self, tmp_path):
        import os

        placed = str(tmp_path / "placed")
        device_dir = compile_cache.DEVICE_CACHE_DIR
        existed = os.path.isdir(device_dir)
        stamp = os.stat(device_dir).st_mtime_ns if existed else None
        before, returned, after, _ = self._run(tmp_path, placed)
        # jax read the variable itself; configure_cache changed nothing:
        # no fingerprint subdirectory, nothing created beside it.
        assert before == returned == after == placed
        assert not os.path.exists(placed) or os.listdir(placed) == []
        # ...and nothing was written under <checkout>/.jax_cache.
        assert os.path.isdir(device_dir) == existed
        if existed:
            assert os.stat(device_dir).st_mtime_ns == stamp

    def test_unset_resolves_to_the_fixed_cpu_path(self, tmp_path):
        import os

        before, returned, after, fp = self._run(tmp_path, None)
        assert before is None
        assert returned == after == os.path.join(
            compile_cache.CPU_CACHE_ROOT, fp
        )
        assert compile_cache.CPU_CACHE_ROOT == os.path.join(
            compile_cache.REPO_ROOT, "tests", ".jax_cache"
        )

    def test_accelerators_take_the_fixed_checkout_path(self, monkeypatch):
        # No device fingerprint in the path (jax's key carries backend,
        # device kind and compiler version) and nothing from a temporary
        # name, a pid, the clock or a boot id.
        import os

        import jax

        updates = {}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.__setitem__(k, v)
        )
        monkeypatch.setattr(
            type(jax.config), "jax_compilation_cache_dir", None,
            raising=False,
        )
        assert compile_cache.configure_cache() == os.path.join(
            compile_cache.REPO_ROOT, ".jax_cache"
        )
        assert updates["jax_compilation_cache_dir"] == os.path.join(
            compile_cache.REPO_ROOT, ".jax_cache"
        )

    def test_an_earlier_call_wins(self):
        # conftest configured this process; a CLI main() called from a
        # test must not move the cache.
        import jax

        placed = jax.config.jax_compilation_cache_dir
        assert placed
        assert compile_cache.configure_cache() == placed
        assert jax.config.jax_compilation_cache_dir == placed


class TestOneUpdateSite:
    """No entry point points the cache anywhere itself."""

    ENTRY_POINTS = (
        "mx_rcnn_tpu/cli/train_cli.py", "mx_rcnn_tpu/cli/eval_cli.py",
        "mx_rcnn_tpu/cli/demo_cli.py", "mx_rcnn_tpu/cli/alternate_cli.py",
        "bench.py", "chip_smoke.py", "__graft_entry__.py",
        "tools/perf_breakdown.py", "tools/train_soak.py", "tools/chaos.py",
        "tools/serve_host.py", "tools/loadgen.py", "tools/soak.py",
        "tools/deploy_watch.py",
        "tests/conftest.py", "tests/_dist_worker.py",
        "tests/_kernels_tpu_worker.py", "tests/_overfit_tpu_worker.py",
    )

    def test_only_compile_cache_updates_the_option(self):
        import os
        import re

        update = re.compile(
            r"update\(\s*[\"']jax_compilation_cache_dir[\"']"
        )
        hits = []
        for root, dirs, files in os.walk(compile_cache.REPO_ROOT):
            dirs[:] = [d for d in dirs if not d.startswith(".")]
            for fn in files:
                if fn.endswith(".py"):
                    path = os.path.join(root, fn)
                    with open(path) as f:
                        if update.search(f.read()):
                            hits.append(
                                os.path.relpath(path, compile_cache.REPO_ROOT)
                            )
        assert hits == ["mx_rcnn_tpu/utils/compile_cache.py"], hits

    def test_every_entry_point_that_compiles_calls_the_one_function(self):
        import os

        for rel in self.ENTRY_POINTS:
            with open(os.path.join(compile_cache.REPO_ROOT, rel)) as f:
                assert "configure_cache()" in f.read(), rel
