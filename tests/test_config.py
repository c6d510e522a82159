"""ForgeConfig: derived policy signatures (single-field sensitivity,
operational-field insensitivity, cross-process stability), the pickle/dict
codec, and the compatibility shims that fold old kwargs into a config."""

import dataclasses
import pickle
import subprocess
import sys

import pytest

from repro.core.config import ForgeConfig
from repro.core.pipeline import ForgePipeline

# one alternative value per field, different from the default
ALT_VALUES = {
    "spec_name": "tpu_v4",
    "max_iterations": 7,
    "best_of_k": 3,
    "use_pallas_exec": False,
    "use_planner": False,
    "warm_start": False,
    "stages_enabled": ("fusion", "autotuning"),
    "use_llm": True,
    "prior_policy": "counts",
    "cost_rank_proposals": False,
    "workers": 4,
    "execution_backend": "process",
    "cache_path": "/tmp/store.json",
    "cache_max_entries": 16,
    "dump_dir": "/tmp/dumps",
    "verify_fastpath": "check",
    "shared_verify_cache_bytes": 0,
    "batch_exec_planning": False,
    "fleet_address": "127.0.0.1:9444",
    "fleet_spawn_workers": 2,
    "fleet_connect_timeout_s": 30.0,
    "fleet_heartbeat_s": 1.0,
    "fleet_heartbeat_timeout_s": 5.0,
    "fleet_max_respawns": 1,
    "fleet_journal_path": "/tmp/fleet.wal",
    "fault_spec": '{"kill_worker_after_jobs":1}',
}


def test_every_field_has_an_alt_value():
    """ALT_VALUES must track the dataclass: a new field without an entry
    here would silently shrink the property tests below."""
    assert set(ALT_VALUES) == {f.name for f in dataclasses.fields(ForgeConfig)}


def test_single_policy_field_changes_signature():
    """Any two configs differing in any single policy field must produce
    different signatures — the auto-derivation guarantee that replaced the
    hand-maintained string (a forgotten knob can't poison the cache)."""
    base = ForgeConfig()
    for f in ForgeConfig.policy_fields():
        changed = base.replace(**{f.name: ALT_VALUES[f.name]})
        assert changed.policy_signature() != base.policy_signature(), f.name


def test_operational_fields_do_not_change_signature():
    """workers/cache location/dump dir cannot change what the pipeline
    produces (workers=1 and workers=N are result-equivalent by design), so
    they must NOT invalidate cached results."""
    base = ForgeConfig()
    assert {f.name for f in ForgeConfig.operational_fields()} == {
        "workers", "execution_backend", "cache_path", "cache_max_entries",
        "dump_dir", "verify_fastpath", "shared_verify_cache_bytes",
        "batch_exec_planning", "fleet_address", "fleet_spawn_workers",
        "fleet_connect_timeout_s", "fleet_heartbeat_s",
        "fleet_heartbeat_timeout_s", "fleet_max_respawns",
        "fleet_journal_path", "fault_spec"}
    for f in ForgeConfig.operational_fields():
        changed = base.replace(**{f.name: ALT_VALUES[f.name]})
        assert changed.policy_signature() == base.policy_signature(), f.name


def test_signature_property_sampled_pairs():
    """Property-style (hypothesis stub-compatible): random single-field
    perturbations over the policy domain always change the signature, and
    equal configs always agree."""
    from hypothesis import given, settings, strategies as st

    policy_names = [f.name for f in ForgeConfig.policy_fields()]

    @settings(max_examples=25)
    @given(idx=st.integers(min_value=0, max_value=len(policy_names) - 1))
    def prop(idx):
        name = policy_names[idx]
        base = ForgeConfig()
        changed = base.replace(**{name: ALT_VALUES[name]})
        assert changed.policy_signature() != base.policy_signature()
        assert base.policy_signature() == ForgeConfig().policy_signature()

    prop()


def test_signature_stable_across_pickle_roundtrip():
    cfg = ForgeConfig(max_iterations=3, stages_enabled=("fusion",),
                      workers=2)
    clone = pickle.loads(pickle.dumps(cfg))
    assert clone == cfg
    assert clone.policy_signature() == cfg.policy_signature()


def test_signature_stable_across_processes():
    """The signature is the cache key prefix shared by process-pool workers:
    a fresh interpreter must derive the identical string (no id()/hash()
    randomization leakage)."""
    cfg = ForgeConfig(best_of_k=2, stages_enabled=("fusion", "autotuning"))
    code = ("import sys, pickle; "
            "sys.stdout.write(pickle.loads(sys.stdin.buffer.read())"
            ".policy_signature())")
    out = subprocess.run([sys.executable, "-c", code],
                         input=pickle.dumps(cfg), capture_output=True,
                         env={"PYTHONPATH": "src"}, cwd=".",
                         check=True).stdout.decode()
    assert out == cfg.policy_signature()


def test_dict_codec_roundtrip():
    cfg = ForgeConfig(max_iterations=2, use_planner=False,
                      stages_enabled=("fusion",))
    d = cfg.to_dict()
    clone = ForgeConfig.from_dict(d)
    assert clone == cfg
    with pytest.raises(ValueError, match="unknown ForgeConfig fields"):
        ForgeConfig.from_dict({"no_such_knob": 1})


def test_validation():
    with pytest.raises(ValueError):
        ForgeConfig(max_iterations=0)
    with pytest.raises(ValueError):
        ForgeConfig(best_of_k=0)
    with pytest.raises(ValueError):
        ForgeConfig(workers=0)
    with pytest.raises(ValueError, match="unknown stage"):
        ForgeConfig(stages_enabled=("not_a_stage",))
    # lists normalize to tuples (hashable, picklable)
    assert ForgeConfig(stages_enabled=["fusion"]).stages_enabled == ("fusion",)


@pytest.mark.parametrize("backend", ["process", "remote"])
def test_spawning_backends_refused_on_a_tpu(backend, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="one process at a time"):
        ForgeConfig(execution_backend=backend)
    assert ForgeConfig(execution_backend="thread").execution_backend == "thread"


# ---------------------------------------------------------------------------
# compatibility shims
# ---------------------------------------------------------------------------

def test_pipeline_kwargs_fold_into_config():
    pipe = ForgePipeline(max_iterations=3, best_of_k=2, use_planner=False,
                         stages_enabled=["fusion", "gpu_specific"])
    assert pipe.config == ForgeConfig(
        max_iterations=3, best_of_k=2, use_planner=False,
        stages_enabled=("fusion", "gpu_specific"))
    assert pipe.T == 3 and pipe.k == 2 and not pipe.use_planner
    assert pipe.policy_signature() == pipe.config.policy_signature()


def test_pipeline_from_config_equals_kwarg_shim():
    a = ForgePipeline(max_iterations=4)
    b = ForgePipeline.from_config(ForgeConfig(max_iterations=4))
    assert a.policy_signature() == b.policy_signature()


def test_llm_presence_reaches_signature():
    class FakeLLM:
        def complete(self, *a, **k):
            return ""

    with_llm = ForgePipeline(llm=FakeLLM())
    without = ForgePipeline()
    assert with_llm.policy_signature() != without.policy_signature()
    # config= path must reflect the llm too
    shim = ForgePipeline(llm=FakeLLM(), config=ForgeConfig())
    assert shim.policy_signature() == with_llm.policy_signature()
