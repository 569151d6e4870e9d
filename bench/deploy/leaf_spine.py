"""The program's leaf-spine fabric from a configuration's ``fabric``
group: ``build(f, links) -> (fabric, routes)``, ``links`` the link
keyword arguments every fabric kind takes."""
from repro.core import LeafSpine


def build(f: dict, links: dict):
    fab = LeafSpine(racks=f["racks"], hosts_per_rack=f["hosts_per_rack"],
                    spines=f["spines"], **links)
    return fab, fab.routes()
