"""Env-override config tier (common/viperutil/config_util.go parity)."""

import json

import pytest

from fabric_tpu.config.localconfig import (apply_env_overrides,
                                           load_node_config)


def test_precedence_and_parsing(tmp_path):
    p = tmp_path / "node.json"
    p.write_text(json.dumps({
        "port": 7051, "host": "127.0.0.1", "ops_port": 9443,
        "raft": {"tick_ms": 100},
    }))
    env = {
        "FABRIC_TPU_PEER_PORT": "9999",                 # json int
        "FABRIC_TPU_PEER_HOST": "0.0.0.0",              # raw string
        "FABRIC_TPU_PEER_OPS_PORT": "9555",             # '_' in key
        "FABRIC_TPU_PEER_RAFT__TICK_MS": "50",          # '__' nesting
        "FABRIC_TPU_PEER_PROFILING": "true",            # json bool
        "FABRIC_TPU_ORDERER_PORT": "1",                 # other role: inert
        "UNRELATED": "x",
    }
    cfg = load_node_config(str(p), "peer", environ=env)
    assert cfg["port"] == 9999
    assert cfg["host"] == "0.0.0.0"
    assert cfg["ops_port"] == 9555
    assert cfg["raft"]["tick_ms"] == 50
    assert cfg["profiling"] is True


def test_override_through_non_object_is_ignored():
    cfg = {"port": 7051}
    out = apply_env_overrides(
        cfg, "peer", environ={"FABRIC_TPU_PEER_PORT__X": "1"})
    assert out["port"] == 7051          # cannot descend into an int


def test_peer_listens_on_env_overridden_port(tmp_path, monkeypatch):
    """Topology check: the peer binds the env-overridden port — config
    changed via environment only, the JSON file untouched."""
    import socket

    from fabric_tpu.comm.rpc import connect
    from fabric_tpu.node.orderer import load_signing_identity
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.node.provision import provision_network

    net = provision_network(str(tmp_path), n_orderers=1,
                            peer_orgs=["Org1"], peers_per_org=1,
                            channel_id="chE")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        new_port = s.getsockname()[1]
    monkeypatch.setenv("FABRIC_TPU_PEER_PORT", str(new_port))
    cfg = load_node_config(net["peers"][0], "peer")
    assert cfg["port"] == new_port
    with open(net["peers"][0]) as f:
        assert json.load(f)["port"] != new_port      # file untouched
    peer = PeerNode(cfg, data_dir=cfg["data_dir"]).start()
    try:
        client = json.load(open(net["clients"]["Org1"]))
        signer = load_signing_identity(
            client["mspid"], client["cert_pem"].encode(),
            client["key_pem"].encode())
        conn = connect(("127.0.0.1", new_port), signer, peer.msps,
                       timeout=5.0)
        try:
            assert conn.call("cscc.channels", {})["channels"] == ["chE"]
        finally:
            conn.close()
    finally:
        peer.stop()


@pytest.mark.parametrize("section,body", [
    ("parallel_commit", {"enabled": True, "early_abort": True}),
    ("device_validate", {"enabled": False})])
def test_a_peer_refuses_to_start_with_a_removed_commit_section(
        tmp_path, section, body):
    """PR 44 took the wave scheduler, the commit window, early abort and
    the fused device validator out.  A config that still carries their
    section stops the peer before it touches anything, whatever the
    section says, and the error names the key."""
    from fabric_tpu.node.peer import main

    p = tmp_path / "peer.json"
    p.write_text(json.dumps({"data_dir": str(tmp_path / "data"),
                             "mspid": "Org1MSP", section: body}))
    with pytest.raises(ValueError, match=f"'{section}'.*removed in PR 44"):
        main([str(p)])
    assert not (tmp_path / "data").exists()
