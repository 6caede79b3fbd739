"""Seconds inside ``repro.deploy.deploy()`` for the cell's deployment:
map, route, program and place on the mesh (host clock)."""


def read(ctx):
    return ctx["deploy_s"]
