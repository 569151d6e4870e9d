"""The program's k-ary fat-tree from a configuration's ``fabric`` group:
``build(f, links) -> (fabric, routes)``, ``links`` the link keyword
arguments every fabric kind takes."""
from repro.core import fat_tree


def build(f: dict, links: dict):
    fab = fat_tree(f["k"], **links)
    return fab, fab
