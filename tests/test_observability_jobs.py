"""Dashboard + jobs CLI tests (SURVEY.md §2B dashboard/job-CLI rows, §5)."""

import json
import os
import sys
import textwrap
import urllib.request

import pytest

import tpu_air


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def test_dashboard_endpoints(air):
    from tpu_air.observability import start_dashboard, stop_dashboard

    if not tpu_air.is_initialized():
        # the session's runtime is gone when test_lease_stress.py's fixture
        # ran earlier in this process (it shuts down whatever it finds)
        tpu_air.init(num_cpus=4, num_chips=8)
    url = start_dashboard(port=0)  # ephemeral port: parallel-test safe
    try:
        cluster = _get_json(f"{url}/api/cluster")
        assert cluster["initialized"]
        assert cluster["resources"]["chip"] == 8
        assert "workers" in cluster and "actors" in cluster

        objects = _get_json(f"{url}/api/objects")
        assert "store_root" in objects
        assert "arena" in objects  # native store active

        version = _get_json(f"{url}/api/version")
        assert version["version"]

        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "tpu_air_chips_total 8" in text
        assert "tpu_air_arena_capacity" in text

        with urllib.request.urlopen(url, timeout=10) as r:
            assert b"tpu_air dashboard" in r.read()
    finally:
        stop_dashboard()


def test_snapshot_tracks_actors(air):
    from tpu_air.observability import snapshot

    @tpu_air.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert tpu_air.get(a.ping.remote()) == "pong"
    snap = snapshot()
    assert len(snap["actors"]) >= 1
    tpu_air.kill(a)


@pytest.fixture()
def job_root(tmp_path, monkeypatch):
    monkeypatch.setenv("TPU_AIR_JOB_ROOT", str(tmp_path / "jobs"))
    return tmp_path


def test_job_submit_wait_logs(job_root, tmp_path):
    """W5 shape: YAML spec -> submit -> status/logs (the reference's
    flan-t5-batch-inference-job-setup.yml flow at test dials)."""
    from tpu_air.job import JobSpec, get_status, list_jobs, logs, submit

    script = tmp_path / "entry.py"
    script.write_text(
        textwrap.dedent(
            """
            import os
            print("job id:", os.environ["TPU_AIR_JOB_ID"])
            print("chips:", os.environ.get("TPU_AIR_NUM_CHIPS"))
            print("JOB DONE")
            """
        )
    )
    spec_path = tmp_path / "job.yml"
    spec_path.write_text(
        textwrap.dedent(
            f"""
            name: test-batch-inference
            compute_config:
              num_chips: 4
              num_cpus: 2
            cluster_env: "test-env:1"
            entrypoint: "{sys.executable} {script}"
            """
        )
    )
    spec = JobSpec.from_yaml(str(spec_path))
    assert spec.name == "test-batch-inference"
    job_id = submit(spec, wait_for_completion=True)
    st = get_status(job_id)
    assert st["status"] == "succeeded"
    assert st["returncode"] == 0
    out = logs(job_id)
    assert "JOB DONE" in out and "chips: 4" in out
    assert any(j["job_id"] == job_id for j in list_jobs())


def test_job_failure_is_reported(job_root, tmp_path):
    from tpu_air.job import submit, get_status

    spec_path = tmp_path / "bad.yml"
    spec_path.write_text(
        f'name: failing-job\nentrypoint: "{sys.executable} -c \'raise SystemExit(3)\'"\n'
    )
    job_id = submit(str(spec_path), wait_for_completion=True)
    st = get_status(job_id)
    assert st["status"] == "failed"
    assert st["returncode"] == 3


def test_job_cli_main(job_root, tmp_path):
    from tpu_air.job.__main__ import main

    script = tmp_path / "ok.py"
    script.write_text("print('hello from cli')")
    spec_path = tmp_path / "cli.yml"
    spec_path.write_text(f'name: cli-job\nentrypoint: "{sys.executable} {script}"\n')
    assert main(["submit", str(spec_path), "--wait"]) == 0
    from tpu_air.job import list_jobs

    jid = [j["job_id"] for j in list_jobs() if j["job_id"].startswith("cli-job")][0]
    assert main(["status", jid]) == 0
    assert main(["logs", jid]) == 0
