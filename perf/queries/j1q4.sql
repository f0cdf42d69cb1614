SELECT COUNT(*) AS n, SUM(x.v1) AS v1, SUM(medium.v2) AS v2, SUM(medium.id2) AS id2, SUM(x.id2 * medium.id2) AS pair FROM x JOIN medium ON x.id5 = medium.id5
