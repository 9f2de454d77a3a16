"""W5 end-to-end: the examples/ job spec through the jobs CLI
(NLP_workloads/Anyscale_job/flan-t5-batch-inference-job-setup.yml:1-7 →
`anyscale job submit` analog)."""

import os

import pytest

from tpu_air.job import jobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_flan_t5_job_submit_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setenv("TPU_AIR_JOB_ROOT", str(tmp_path))
    spec = jobs.JobSpec.from_yaml(os.path.join(REPO, "examples", "flan_t5_job.yml"))
    assert spec.name == "flan-t5-batch-inference"
    assert spec.compute_config == {"num_cpus": 8, "num_chips": 8}
    spec.working_dir = REPO
    job_id = jobs.submit(spec, wait_for_completion=True)
    st = jobs.get_status(job_id)
    log = jobs.logs(job_id)
    assert st["status"] == "succeeded", f"job failed:\n{log[-3000:]}"
    assert "generated_output" in log and "generated 19 outputs" in log


def _run_example(script, *args, timeout=500):
    import subprocess, sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_xgboost_e2e_example():
    proc = _run_example("xgboost_e2e.py", "--rows", "400", "--port", "8217",
                        timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "HTTP prediction" in proc.stdout


@pytest.mark.slow
def test_segformer_example():
    proc = _run_example("segformer_finetune.py", "--images", "8", "--epochs", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "segmentation maps" in proc.stdout


@pytest.mark.slow
def test_tune_hpo_example():
    proc = _run_example("tune_hpo_t5.py", "--trials", "2")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "best eval_loss" in proc.stdout


def test_strict_mode_fails_loudly_without_assets(monkeypatch):
    """--strict must exit nonzero with the REAL error
    when assets are missing — never a silent synthetic fallback.  Forced
    offline so the failure is fast and deterministic."""
    import subprocess, sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    env.update(HF_HUB_OFFLINE="1", HF_DATASETS_OFFLINE="1",
               HF_HOME=str(os.path.join(os.getcwd(), "nonexistent-hf-home")))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "flan_t5_batch_inference.py"), "--strict"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0, "strict run with no assets must fail"
    out = proc.stdout + proc.stderr
    assert "falling back to synthetic" not in out
    assert "Error" in out or "error" in out


def test_strict_and_smoke_are_mutually_exclusive():
    import subprocess, sys

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "flan_t5_batch_inference.py"),
         "--strict", "--smoke"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode != 0
    assert "mutually exclusive" in proc.stderr


@pytest.mark.slow
def test_long_context_lm_example():
    """W-beyond: sequence-parallel long-context LM training (ring attention
    + Pallas kernels) on the virtual mesh — the capability the reference
    caps at 512 tokens."""
    proc = _run_example("long_context_lm.py", "--seq-len", "256", "--sp", "2",
                        "--steps", "8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "sequence-parallel training OK" in proc.stdout


@pytest.mark.slow
def test_inference_architectures_example():
    """W7: the reference's five-architecture comparison arc
    (Scaling_batch_inference.ipynb:cc-136) runs end to end."""
    proc = _run_example("inference_architectures.py", "--images", "12")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "vs sequential" in proc.stdout and "BatchPredictor" in proc.stdout


@pytest.mark.slow
def test_multihost_training_example():
    proc = _run_example("multihost_training.py", timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIHOST-EXAMPLE-OK" in proc.stdout
    assert "hosts=2" in proc.stdout
