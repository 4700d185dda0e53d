"""The db endpoint's rule set of BASELINE config #3: ``rules``
selector allows, each from one client service over a 6-port range, a
namespace-wide 5432/TCP allow, a CIDR range, one deny, one L7 redirect
and an egress DNS allow.  It uses each rule form of upstream's policy
language (endpoint selectors, ``endPort`` ranges, ``fromCIDR``,
``ingressDeny``, L7 ``http`` rules, ``toEntities``)."""


def rules(n_identities: int, params: dict) -> list:
    n_rules = int(params["rules"])
    out = []
    group = max(n_identities // n_rules, 1)
    for r in range(n_rules):
        ports = [{"port": str(1000 + r * 7), "protocol": "TCP",
                  "endPort": 1000 + r * 7 + 5}]
        sel = {"matchLabels": {"app": f"svc{r * group}"}}
        out.append({
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [sel],
                         "toPorts": [{"ports": ports}]}],
        })
    out.append({
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [
            {"fromEndpoints": [{"matchLabels": {"ns": "default"}}],
             "toPorts": [{"ports": [{"port": "5432",
                                     "protocol": "TCP"}]}]},
            {"fromCIDR": ["192.168.0.0/16"],
             "toPorts": [{"ports": [{"port": "8000", "endPort": 8999}]}]},
            {"fromEndpoints": [{"matchLabels": {"ns": "default"}}],
             "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}],
                          "rules": {"http": [{"method": "GET"}]}}]},
        ],
        "ingressDeny": [
            {"fromEndpoints": [{"matchLabels": {"app": "svc0"}}],
             "toPorts": [{"ports": [{"port": "22", "protocol": "TCP"}]}]},
        ],
        "egress": [
            {"toEntities": ["world"],
             "toPorts": [{"ports": [{"port": "53", "protocol": "UDP"}]}]},
        ],
    })
    return out
