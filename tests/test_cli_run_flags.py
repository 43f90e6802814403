"""`sub run -i/-r` semantics (reference internal/cli/run.go:16-104 +
tui/common.go:158-245): -i creates `{name}-{N+1}` next to the highest
existing `{name}-N`; -r deletes any existing object first; together they
are rejected. Driven through the plain CLI path against the fake
cluster (subprocess, non-tty)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(argv, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "substratus_tpu.cli.main"] + argv,
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd,
    )


def _workdir(tmp_path):
    (tmp_path / "train.py").write_text("print('hi')\n")
    (tmp_path / "Dockerfile").write_text("FROM scratch\nCOPY . /src\n")
    (tmp_path / "model.yaml").write_text(
        """
apiVersion: substratus.ai/v1
kind: Model
metadata:
  name: vmodel
spec:
  image: registry.local/vmodel
  command: ["python", "train.py"]
""".lstrip()
    )
    return tmp_path


def test_increment_and_replace_flags(tmp_path):
    wd = _workdir(tmp_path)
    # The fake cluster is in-process per invocation, so drive one python
    # process that runs the three flows back-to-back against ONE fake.
    script = f"""
import sys
sys.argv = ["sub"]
from substratus_tpu.cli.commands import _client
from substratus_tpu.cli.root import build_parser

parser = build_parser()

def run(*extra):
    args = parser.parse_args(
        ["run", "-f", "{wd}/model.yaml", "-d", "{wd}", "--fake",
         "--plain", *extra]
    )
    return args.func(args)

assert run() == 0
client = _client(parser.parse_args(["get", "--fake"]))
assert client.get("Model", "default", "vmodel")

assert run("-i") == 0                      # -> vmodel-1
assert client.get("Model", "default", "vmodel-1")
assert run("--increment") == 0             # -> vmodel-2
assert client.get("Model", "default", "vmodel-2")

before = client.get("Model", "default", "vmodel")["metadata"]["uid"]
assert run("-r") == 0                      # delete + recreate
after = client.get("Model", "default", "vmodel")["metadata"]["uid"]
assert after != before, (before, after)
print("FLAGS-OK")
"""
    proc = _run_cli(["version"], wd)  # warm import sanity
    assert proc.returncode == 0, proc.stderr
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, env=env, cwd=wd,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FLAGS-OK" in proc.stdout


def test_increment_replace_mutually_exclusive(tmp_path):
    wd = _workdir(tmp_path)
    proc = _run_cli(
        ["run", "-f", "model.yaml", "--fake", "--plain", "-i", "-r"], wd
    )
    assert proc.returncode != 0
    assert "not allowed with" in proc.stderr