"""One deployment, as `configs/<name>.json` describes it: provisioned
identities and node configs, and (for a served cell) the nodes as OS
processes with what a client needs to talk to them.

Copied from `chip_smoke.py`'s `Network` (the smoke stays the program's;
the yardstick must not move when it does), with the deployment's sizes
read from the configuration file.  Never imports jax.
"""

from __future__ import annotations

import itertools
import json
import os
import time
import urllib.request


class DeploymentError(Exception):
    pass


def http_json(method: str, url: str, body=None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def write_json(path: str, doc: dict) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


class Deployment:
    """Provisioned from a configuration dict (`configs/<name>.json`)."""

    def __init__(self, base: str, config: dict, repo: str,
                 device_peer_extra: dict):
        from fabric_tpu.config import BatchConfig
        from fabric_tpu.node.provision import free_ports, provision_network
        from fabric_tpu.testing.procnet import load_client

        self.base = base
        self.config = config
        self.channel = config["channel"]
        self.chaincode = config["chaincode"]["name"]
        self.orgs = list(config["peer_orgs"])
        self.device_org = config["device_org"]
        self.procs = {}          # name -> Popen
        b = config["batch"]
        n_clients = int(config["client_identities"])
        self.net = provision_network(
            base, n_orderers=int(config["orderers"]), peer_orgs=self.orgs,
            peers_per_org=int(config["peers_per_org"]),
            channel_id=self.channel,
            batch=BatchConfig(int(b["max_message_count"]),
                              int(b["absolute_max_bytes"]),
                              int(b["preferred_max_bytes"]),
                              float(b["timeout_s"])),
            clients_per_org=-(-n_clients // len(self.orgs)))
        # one environment for every node: what tells the device peer
        # apart is its config, and JAX there takes the accelerator by
        # default.  The package is run from the checkout, not installed.
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [repo] + self.env.get("PYTHONPATH", "").split(os.pathsep))
        self.peer_cfg = {}       # org -> node cfg dict
        self.peer_cfg_path = {}  # org -> path
        self.ops = {}            # org -> "http://host:port"
        ops_ports = free_ports(len(self.orgs))
        for path, port in zip(self.net["peers"], ops_ports):
            cfg = read_json(path)
            cfg["ops_port"] = port
            # node defaults: every peer verifies every signature itself
            # (the provisioner's attestation-trust opt-in is taken out)
            cfg.pop("verify_once", None)
            if cfg["mspid"] == self.device_org:
                cfg.update(config["device_peer"])
                cfg.update(device_peer_extra)
            else:
                cfg.update(config["reference_peer"])
            write_json(path, cfg)
            self.peer_cfg[cfg["mspid"]] = cfg
            self.peer_cfg_path[cfg["mspid"]] = path
            self.ops[cfg["mspid"]] = f"http://127.0.0.1:{port}"
        self.peer_addr = {org: (cfg["host"], cfg["port"])
                          for org, cfg in self.peer_cfg.items()}
        # the enrolled identities, org by org in turn
        pool = [p for turn in itertools.zip_longest(
            *(self.net["client_pool"][org] for org in self.orgs))
            for p in turn if p is not None][:n_clients]
        self.client_cfgs = pool
        self.clients = [load_client(p)[1] for p in pool]
        cc, self.signer, self.msps = load_client(
            self.net["clients"][self.device_org])
        self.orderers = [tuple(o) for o in cc["orderers"]]
        # what a generator's worker process needs, as a file
        self.file = os.path.join(base, "deployment.json")
        write_json(self.file, {
            "peer_cfgs": [self.peer_cfg_path[o] for o in self.orgs],
            "client_cfgs": pool})

    # -- processes ----------------------------------------------------------

    def start_orderers(self) -> None:
        from fabric_tpu.testing.procnet import spawn_node
        for path in self.net["orderers"]:
            name = os.path.basename(path)[:-5]
            self.procs[name] = spawn_node(
                "fabric_tpu.node.orderer", path, env=self.env,
                log_path=os.path.join(self.base, name + ".log"))

    def start_peer(self, org: str, module: str = "fabric_tpu.node.peer"):
        from fabric_tpu.testing.procnet import spawn_node
        self.procs["peer" + org] = spawn_node(
            module, self.peer_cfg_path[org], env=self.env,
            log_path=os.path.join(self.base, f"peer{org}.log"))

    def stop(self) -> None:
        from fabric_tpu.testing.procnet import stop_nodes
        stop_nodes(self.procs.values())

    def assert_alive(self) -> None:
        for name, proc in self.procs.items():
            if proc.poll() is not None:
                raise DeploymentError(
                    f"{name} exited with {proc.returncode}:\n"
                    + self.log_tail(name))

    def log_tail(self, name: str, n: int = 3000) -> str:
        try:
            with open(os.path.join(self.base, name + ".log"), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return "(no log)"

    # -- what a client can observe ------------------------------------------

    def state(self, org: str) -> dict:
        return http_json("GET", self.ops[org] + "/state")

    def provider_status(self, org: str) -> dict:
        return self.state(org)["provider"]

    def metrics_text(self, org: str) -> str:
        return http_text(self.ops[org] + "/metrics")

    def wait_ops(self, org: str, deadline_s: float) -> dict:
        """provider_status once the peer's ops server answers."""
        deadline = time.monotonic() + deadline_s
        while True:
            self.assert_alive()
            try:
                return self.provider_status(org)
            except OSError:
                if time.monotonic() > deadline:
                    raise DeploymentError(
                        f"{org}'s ops endpoint never came up:\n"
                        + self.log_tail("peer" + org))
                time.sleep(0.25)

    def statuses(self) -> dict:
        from fabric_tpu.testing.procnet import node_status
        return {org: node_status(addr, self.signer, self.msps)
                for org, addr in self.peer_addr.items()}

    def wait_heights(self, want: int, deadline_s: float) -> dict:
        from fabric_tpu.testing.procnet import wait_peer_heights
        return wait_peer_heights(self.peer_addr, self.signer, self.msps,
                                 want, deadline_s=deadline_s)

    def fetch_blocks(self, org: str, lo: int, hi: int) -> list:
        """Blocks [lo, hi) as the peer's qscc serves them."""
        from fabric_tpu.comm import connect
        from fabric_tpu.protocol.types import Block
        conn = connect(self.peer_addr[org], self.signer, self.msps,
                       timeout=10.0)
        try:
            return [Block.deserialize(conn.call(
                "qscc.block_by_number",
                {"channel": self.channel, "number": n},
                timeout=60.0)["block"]) for n in range(lo, hi)]
        finally:
            conn.close()


def block_flags(block) -> list:
    """[(txid, validation code)] of one committed block."""
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.txflags import TxFlags
    from fabric_tpu.protocol.types import META_TXFLAGS
    codes = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS]).codes()
    return [(wire.envelope_summary(raw)[2], int(code))
            for raw, code in zip(block.data, codes)]
