"""Config loader tests (ref behavior: config_loader.rs auto-create, getters,
fallbacks — SURVEY.md §2.2)."""

import json

import pytest

from relayrl_tpu.config import (
    DEFAULT_CONFIG_FILENAME,
    ConfigLoader,
    default_config,
)


class TestAutoCreate:
    def test_creates_default_in_cwd(self, tmp_cwd):
        loader = ConfigLoader("REINFORCE")
        created = tmp_cwd / DEFAULT_CONFIG_FILENAME
        assert created.is_file()
        on_disk = json.loads(created.read_text())
        assert "algorithms" in on_disk and "server" in on_disk
        assert loader.get_max_traj_length() == 1000

    def test_no_create_when_disabled(self, tmp_cwd):
        ConfigLoader("REINFORCE", create_if_missing=False)
        assert not (tmp_cwd / DEFAULT_CONFIG_FILENAME).exists()

    def test_explicit_path(self, tmp_path):
        path = tmp_path / "sub" / "cfg.json"
        loader = ConfigLoader("REINFORCE", config_path=path)
        assert path.is_file()
        assert loader.get_train_server().port == "50051"


class TestGetters:
    def test_algorithm_params(self, tmp_cwd):
        loader = ConfigLoader("REINFORCE")
        params = loader.get_algorithm_params()
        assert params["gamma"] == pytest.approx(0.98)
        assert params["traj_per_epoch"] == 8
        assert params["with_vf_baseline"] is False

    def test_case_insensitive_algo(self, tmp_cwd):
        loader = ConfigLoader("reinforce")
        assert loader.get_algorithm_params()["gamma"] == pytest.approx(0.98)

    def test_user_overrides_merge_over_defaults(self, tmp_path):
        path = tmp_path / "cfg.json"
        cfg = default_config()
        cfg["algorithms"]["REINFORCE"] = {"gamma": 0.5}
        path.write_text(json.dumps(cfg))
        loader = ConfigLoader("REINFORCE", config_path=path)
        params = loader.get_algorithm_params()
        assert params["gamma"] == 0.5
        assert params["pi_lr"] == pytest.approx(3e-4)  # default survives

    def test_endpoints(self, tmp_cwd):
        loader = ConfigLoader()
        assert loader.get_train_server().address == "tcp://127.0.0.1:50051"
        assert loader.get_traj_server().address == "tcp://127.0.0.1:7776"
        assert loader.get_agent_listener().address == "tcp://127.0.0.1:7777"

    def test_endpoint_fallback_on_missing_key(self, tmp_path):
        path = tmp_path / "cfg.json"
        path.write_text(json.dumps({"server": {}}))
        loader = ConfigLoader(config_path=path)
        assert loader.get_traj_server().port == "7776"

    def test_model_paths_not_swapped(self, tmp_cwd):
        # Ref bug (config_loader.rs:504-534): fallbacks return client/server
        # paths crossed. Ours must not.
        loader = ConfigLoader()
        assert "client" in loader.get_client_model_path()
        assert "server" in loader.get_server_model_path()

    def test_idle_timeout_seconds(self, tmp_cwd):
        loader = ConfigLoader()
        assert loader.get_grpc_idle_timeout_s() == pytest.approx(30.0)

    def test_tb_params(self, tmp_cwd):
        params = ConfigLoader().get_tb_params()
        assert params["global_step_tag"] == "Epoch"
        assert "_comment1" not in params

    def test_plugin_algorithm_warns(self, tmp_cwd):
        with pytest.warns(UserWarning):
            ConfigLoader("MY_CUSTOM_ALGO")

    def test_learner_params(self, tmp_cwd):
        params = ConfigLoader().get_learner_params()
        assert params["mesh"]["dp"] == -1
        assert params["precision"] == "float32"  # CPU-safe default; the benchmark configurations set bf16


class TestEnvDirAnchoring:
    """Default-named run artifacts anchor under env_dir, not the caller's
    cwd (VERDICT r3 #8: example runs were leaving server_model.rlx,
    checkpoints/ and logs/ at the repo root)."""

    def test_algorithm_artifacts_anchor_under_env_dir(self, tmp_cwd,
                                                      tmp_path):
        import os

        from relayrl_tpu.algorithms import build_algorithm

        env_dir = tmp_path / "run"
        algo = build_algorithm("REINFORCE", env_dir=str(env_dir),
                               obs_dim=3, act_dim=2, hidden_sizes=[8],
                               with_vf_baseline=False)
        assert algo.server_model_path == os.path.join(str(env_dir),
                                                      "server_model.rlx")
        # the logger already landed its run dir under env_dir/logs
        assert str(algo.logger.output_dir).startswith(
            os.path.join(str(env_dir), "logs"))
        # absolute configured paths pass through untouched
        from relayrl_tpu.algorithms.base import anchor_path

        assert anchor_path("/abs/model.rlx", str(env_dir)) == "/abs/model.rlx"
        assert anchor_path("rel.rlx", None) == "rel.rlx"

    def test_server_checkpoint_dir_anchors_under_env_dir(self, tmp_cwd,
                                                         tmp_path):
        import os

        from relayrl_tpu.runtime.server import TrainingServer

        env_dir = tmp_path / "run2"
        server = TrainingServer(
            "REINFORCE", obs_dim=3, act_dim=2, env_dir=str(env_dir),
            start=False, hyperparams={"hidden_sizes": [8]})
        assert server._checkpoint_dir == os.path.join(str(env_dir),
                                                      "checkpoints")
