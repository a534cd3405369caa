"""Share of the certificate chains' leaf links validated in the window
(the org CA's signature over an identity's certificate) whose signature
the device checked — deferred by the MSP as a P-256 item to the
validator's batch — and not OpenSSL on the host, inside the validation
(`msp_chain_signatures_total{where}`: device ÷ device + host, every
MSP): ~100 where every block brings each CA enough unseen creators for
the rows lane, 0 where the MSPs validate one identity at a time.  None
on a program without the counter, or where the window validated no
chain."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "msp_chain_signatures_total" not in after:
        return None
    links = prom_delta(before, after, "msp_chain_signatures_total")
    if links <= 0:
        return None
    return 100.0 * prom_delta(before, after, "msp_chain_signatures_total",
                              where="device") / links
